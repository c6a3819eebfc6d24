"""Milliseconds per step of the optimizer on the device: ops under the
program's ``clip`` and ``optimizer`` scopes (``train/step.py``: global
norm and scale, AdamW and the parameter add), averaged over the chips.
Nothing to read where the program carries neither scope."""
import scopes


def read(run: dict):
    def seconds(s):
        if not set(scopes.OPTIMIZER) & set(s["present"]):
            return None
        return s["phases"]["optimizer"]
    return scopes.ms_per_step(run, seconds)
