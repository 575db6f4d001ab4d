"""Seconds from process start until the window opens: generating the
index, placing it with the program's loader, compiling and warming."""


def read(rec):
    return rec["setup_s"]
