"""Milliseconds per step of the attention core on the device: ops under
the program's ``sdpa`` scope (``models/attention.py``: plain S x S
attention or the chunked flash path with its custom backward), in the
forward, the recompute and the backward, averaged over the chips.
Nothing to read where the program carries no such scope."""
import scopes


def read(run: dict):
    def seconds(s):
        if scopes.ATTN_CORE not in s["present"]:
            return None
        return s["attn_core_s"]
    return scopes.ms_per_step(run, seconds)
