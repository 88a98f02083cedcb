"""Host clock around ``lower().compile()`` of the step program: a compile
on a cell's first run in a checkout, a load from the cache after."""


def read(run):
    return run["worker"]["compile"]["step_load_s"]
