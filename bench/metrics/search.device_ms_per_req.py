"""Device milliseconds per completed request: the summed self times of the
device's ops in the traced window over the requests completed in it."""


def read(ctx):
    if not ctx.completed or not ctx.op_seconds:
        return None
    return 1e3 * sum(ctx.op_seconds.values()) / ctx.completed
