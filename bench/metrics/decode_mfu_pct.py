"""Device, whole decode step: FLOPs the window's decode ticks require, over
their host-clock duration times the chip's bf16 peak.

The FLOPs of a tick come from the model's family (``decode_flops``, from
the shapes and the live rows' context lengths); a family that counts none
gets no reading."""

from bench import families


def read(run):
    w0, w1 = run.log.window
    ticks = [t for t in run.log.ticks if w0 <= t.start and t.end <= w1]
    dur = sum(t.end - t.start for t in ticks)
    flops = getattr(families.family(run.model), "decode_flops", None)
    if flops is None or not dur:
        return None
    need = sum(flops(run.model, t.kv_lens) for t in ticks)
    return 100.0 * need / (dur * run.peaks["bf16_flops_per_s"])
