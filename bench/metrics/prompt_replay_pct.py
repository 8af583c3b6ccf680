"""Scheduler and admission: share of prompt tokens fed through decode ticks
instead of prefill.  Prompts bucket down to a prefill length and replay the
rest one token per tick; this counts, over requests that arrived in the
window and were admitted, the ticks each spent between admission and its
first token, against all their prompt tokens."""


def read(run):
    recs = [r for r in run.log.in_window() if r.tokens]
    prompt = sum(r.prompt_len for r in recs)
    if not prompt:
        return None
    return 100.0 * sum(r.replay_ticks for r in recs) / prompt
