"""Channels layer (``core/channels.py``: Ch_mir, Ch_req, combiners): the
cell's exact message counter (named by its traffic file) over the
supersteps of the first window job."""
UNIT = "msgs"


def read(rec):
    j = rec["jobs"][0]
    if rec["counter"] not in j["stats"] or not j["supersteps"]:
        return None
    return int(j["stats"][rec["counter"]]) / j["supersteps"]
