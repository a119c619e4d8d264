"""Share of the traced window, in %, in which the device idled while the
engine was inside ``engine.readback``: the concatenation of a round's token
ids and their one device-to-host transfer (``slobench/idle.py``)."""
from slobench import idle


def read(run):
    return idle.share(run, "engine.readback")
