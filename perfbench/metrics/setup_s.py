"""Set-up: process start to the window's start (weights, corpus, fleet,
compile or compile-cache loads, warm-up traffic), on the host clock."""


def read(r):
    return r.setup_s
