"""Host seconds of the events at t=0: ``ClientPool.start``, its initial
selection, the state upload and the first tick, with their compiles or
cache loads."""


def read(ctx):
    return ctx.setup.get("start_s")
