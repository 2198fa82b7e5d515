"""``setup_s``: seconds from the process's start to the window's: weights,
images, the cloud server, the edge sessions and the warm-up."""


def read(rec):
    return rec["setup_s"]
