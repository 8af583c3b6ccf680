"""Model step, prefill: device time of the prefill executables per 1000
prefilled positions (the bucket lengths admissions prefilled), over the
admissions inside the traced window.  A prefill executable is a module in a
``bench.admit`` span whose name matches ``PREFILL``."""

from bench import trace as tr

# jitted prefill of the engine (a lambda, so XLA names it after one)
PREFILL = r"^jit__lambda"


def read(run):
    positions = sum(a.prefill_len for a in run.traced_admissions)
    if run.trace is None or not positions:
        return None
    dev = sum(e.dur for mods in run.trace.modules
              for evs in tr.within(mods, run.trace.spans, "bench.admit",
                                   PREFILL).values() for e in evs)
    if not dev:
        return None
    return 1e3 * dev / len(run.trace.modules) / (positions / 1000.0)
