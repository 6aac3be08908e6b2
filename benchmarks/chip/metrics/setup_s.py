"""Set-up time: from the start of the process to the end of the
warm-up (loading, making the data, building the state, compiling or
loading every program the window uses).  Host clock."""


def read(run):
    return run.setup_s
