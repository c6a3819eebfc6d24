"""Milliseconds per step of the aggregator's own compute on the device:
the ops under the program's ``aggregate`` scope (``core/aggregator.py``,
``core/reducers.py``: packing, casts, codec, adds) other than its
collectives, which ``agg_ms`` reads, averaged over the chips.  Nothing
to read where the program carries no such scope."""
import scopes


def read(run: dict):
    def seconds(s):
        if scopes.AGGREGATE not in s["present"]:
            return None
        return s["phases"]["aggregation"]
    return scopes.ms_per_step(run, seconds)
