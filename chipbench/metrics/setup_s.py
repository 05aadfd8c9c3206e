"""setup_s (s): from process start to the first timed call: network
generation, the program's build, compilation (from the cache after a
checkout's first run), and the presim with one untimed call."""


def read(run):
    return run.setup_s
