"""End to end: process start to the window's opening: imports, platform
start, data, template upload and the job's first trial, which pays the
compiles (first run of a checkout) or the cache loads."""


def read(run):
    return run["setup_s"]
