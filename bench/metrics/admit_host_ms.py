"""Scheduler and admission: host time per admission, from the program's
own spans.  The mean, over the ``serve.admit`` spans inside the window, of
each span's duration less its ``serve.sync`` child (the first token read
back, on the path where prefill emits it): what is left is the host's
dispatch of the prefill and of seeding the pool, whose device work may run
later."""

from bench import program_spans


def read(run):
    return program_spans.self_ms(run, "serve.admit", "serve.sync")
