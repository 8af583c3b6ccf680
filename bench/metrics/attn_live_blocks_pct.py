"""Kernels: share of the fused paged attention kernel's grid that holds
context, from the program's own counts.  Over the ``serve.step`` spans
inside the window, the sum of ``kv_blocks`` (table columns holding a valid
position, ``ceil(kv_len / block_size)`` per slot, idle slots at one) over
the sum of ``table_blocks`` (slots times table width), in percent.  A
program whose ticks carry no such counts gives nothing."""

from bench import program_spans


def read(run):
    steps = [s for s in program_spans.in_window(
                 run, program_spans.recorded(run), "serve.step")
             if "kv_blocks" in s.counts and "table_blocks" in s.counts]
    table = sum(s.counts["table_blocks"] for s in steps)
    if not table:
        return None
    return 100.0 * sum(s.counts["kv_blocks"] for s in steps) / table
