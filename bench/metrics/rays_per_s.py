"""Pixel rays of all frames served, over the host time from the first
submit to the last frame handed back."""


def read(ctx):
    served = ctx["served"]
    if not served:
        return None
    rays = sum(r.cam.height * r.cam.width for r in served)
    return rays / ctx["window_host_s"]
