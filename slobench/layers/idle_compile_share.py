"""Share of the traced window, in %, in which the device idled while the
engine was inside ``engine.compile``: the first call of a step shape, which
traces and compiles the program or loads it from the cache
(``slobench/idle.py``)."""
from slobench import idle


def read(run):
    return idle.share(run, "engine.compile")
