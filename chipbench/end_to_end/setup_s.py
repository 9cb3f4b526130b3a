"""Process start to the first timed call: imports, keys and signed
requests, the program's table build and upload, compile or cache load,
the untimed warm-up calls."""


def read(run):
    return run["setup_s"]
