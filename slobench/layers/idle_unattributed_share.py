"""Share of the traced window, in %, in which the device idled while the
engine was in none of its spans: the blind spot of the engine's spans
(``slobench/idle.py``)."""
from slobench import idle


def read(run):
    return idle.share(run, idle.UNATTRIBUTED)
