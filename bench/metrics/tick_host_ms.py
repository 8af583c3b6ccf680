"""Engine host loop: host time per decode tick, from the program's own
spans.  The mean, over the ``serve.step`` spans inside the window, of each
span's duration less its ``serve.sync`` child (the decode dispatched and
its tokens read back, the host blocked on the device): what is left is
preparing the tick's inputs and committing its tokens."""

from bench import program_spans


def read(run):
    return program_spans.self_ms(run, "serve.step", "serve.sync")
