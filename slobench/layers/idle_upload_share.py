"""Share of the traced window, in %, in which the device idled while the
engine was inside ``engine.upload``: the host-to-device uploads of one
dispatch's operands (``slobench/idle.py``)."""
from slobench import idle


def read(run):
    return idle.share(run, "engine.upload")
