"""The output check that decides ``correct``.

After the window, a sample of the requests that arrived in it and finished
is drawn from the seed, always holding the one that served the most tokens.
The plain reference (``bench/reference.py``) runs once over each prompt
followed by its served tokens, and the number compared is the widest gap,
over every served token of the sample, by which the served token's
reference logit lies below the reference's best (``logit_gap_max``).
Greedy serving of the same arithmetic gives gaps near 0; a token that a
lower precision or a broken cache put first gives a wide one.

A control puts the reference in the program's place at a lower precision
(``reference.Reference(precision="int8" | "fp8")``) and reads, at each
position of the same sequences, the gap of the token the control puts
first.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from bench import reference

SAMPLE_SALT = 7919      # the sample's generator: seeded by (seed, salt)


def _pick(recs, rng: np.random.Generator, min_tokens: int,
                    max_requests: int) -> List:
    """Finished requests to check: the one that served the most tokens,
    then others drawn by ``rng`` until ``min_tokens`` served tokens are in
    the sample or ``max_requests`` requests are."""
    done = [r for r in recs if not r.failed and r.tokens]
    if not done:
        return []
    done.sort(key=lambda r: (-r.out_len, r.rid))
    pick = [done[0]]
    rest = [done[i] for i in rng.permutation(np.arange(1, len(done)))]
    for r in rest:
        if sum(p.out_len for p in pick) >= min_tokens \
                or len(pick) >= max_requests:
            break
        pick.append(r)
    return pick


def _inputs(picked, prompts: Dict[int, np.ndarray],
                 served: Dict[int, np.ndarray]):
    """Sequences (prompt + served tokens but the last) and the positions
    whose logits predict each served token."""
    seqs, rows = [], []
    for r in picked:
        p, s = prompts[r.rid], served[r.rid]
        seqs.append(np.concatenate([p, s[:-1]]).astype(np.int32))
        rows.append(np.arange(len(p) - 1, len(p) - 1 + len(s)))
    return seqs, rows


def sample(log, served: Dict[int, np.ndarray], traffic, seed: int,
           min_tokens: int, max_requests: int):
    """(picked records, prompts, served tokens) of the check's sample."""
    recs = [r for r in log.in_window() if r.rid in served]
    rng = np.random.default_rng([int(seed) % (2 ** 63), SAMPLE_SALT])
    picked = _pick(recs, rng, min_tokens, max_requests)
    prompts = {r.rid: traffic.tokens(r.rid, r.prompt_len) for r in picked}
    return picked, prompts, {r.rid: served[r.rid] for r in picked}


def readings(model: dict, seed: int, picked, prompts, served,
             controls=()) -> Dict[str, float]:
    """``logit_gap_max`` of the served tokens and, for each precision in
    ``controls``, ``<precision>_control_gap_max``: the control's reading
    over the same positions."""
    seqs, rows = _inputs(picked, prompts, served)
    ref = reference.Reference(model, seed).logits(seqs, rows)
    gaps = [reference.served_gaps(lg, served[r.rid])
            for lg, r in zip(ref, picked)]
    out = {"logit_gap_max": float(max(g.max() for g in gaps)),
           "tokens_checked": int(sum(len(g) for g in gaps)),
           "tokens_off_argmax": int(sum((g > 0).sum() for g in gaps)),
           "requests_checked": len(picked)}
    for precision in controls:
        ctl = reference.Reference(model, seed, precision).logits(seqs, rows)
        cg = [reference.control_gaps(a, b) for a, b in zip(ref, ctl)]
        out[f"{precision}_control_gap_max"] = float(max(g.max() for g in cg))
    return out


def verdict(values: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """Each compared number beside its limit, and whether all hold."""
    table = {k: {"value": values.get(k), "limit": limits.get(k)}
             for k in limits}
    ok = bool(table) and all(
        v["value"] is not None and v["limit"] is not None
        and v["value"] <= v["limit"] for v in table.values())
    return ok, table


def describe(table) -> List[str]:
    return [f"check {k}: {v['value']} (limit {v['limit']})"
            for k, v in table.items()]
