"""Partitioner layer (``graph/partitioner.py`` via ``Engine.partition``):
host seconds of the set-up's one ``Engine.partition`` call."""
UNIT = "s"


def read(rec):
    return rec["partition_s"]
