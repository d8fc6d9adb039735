"""Set-up: seconds from the process's start to the window's start --
imports, weights drawn on the device, compiling or loading every program
from the compilation cache, warm-up."""


def read(r):
    return r.setup_s
