"""``setup_s``: seconds from process start to the window's start (imports,
corpus, compile-cache loads and the warm-up), host clock."""


def read(run):
    return run.setup_s
