"""``setup_s``: seconds from process start to the start of the window
(imports, design build, emission, simulator build, warm-up and compile or
cache load), host clock."""


def read(run):
    return run.setup_s
