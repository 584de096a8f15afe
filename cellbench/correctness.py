"""How `correct` is decided for a served model.

Once the window has closed, a sample of the requests it finished —
drawn from the seed, the longest always in it — is given to the plain
reference: one float32 forward pass over each prompt with the tokens
the program SERVED for it.  At every served position the reference
says how far the served token's logit lies below its own best, in
units of that position's logit spread (standard deviation over the
vocabulary).  Greedy decoding in exact arithmetic serves the
reference's best token, gap 0; bfloat16 rounding flips near-ties and
leaves small gaps; a lower precision, a wrong token, a stale or
misplaced KV page leave wide ones.

Two numbers are compared, each with a limit of its own from the
cell's file (set from chip readings, PERF.md section 2):

- ``served_gap_max``  — the widest gap over the sampled tokens;
- ``served_gap_mean`` — the mean gap over them (steady from seed to
  seed where the widest swings).

The control (`control_numbers`) reads the same two numbers for the
token the reference computed in float8 puts first at each position of
the same prompts and tokens: the step below the configuration's
bfloat16.  It has to come out as not correct.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

NUMBERS = ("served_gap_max", "served_gap_mean")


def pick_sample(rows: Sequence[dict], seed: int, n: int) -> List[dict]:
    """``n`` of the finished requests: the longest (prompt + served)
    and ``n - 1`` others drawn from the seed."""
    rows = [r for r in rows if r["ok"]]
    if not rows:
        return []
    longest = max(rows, key=lambda r: (r["prompt_len"] + len(r["tokens"]),
                                       -r["index"]))
    rest = [r for r in rows if r is not longest]
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    take = min(n - 1, len(rest))
    picked = [rest[i] for i in
              sorted(rng.choice(len(rest), take, replace=False))]
    return [longest] + picked


def _gap_rows(lg, tokens):
    import jax.numpy as jnp
    chosen = jnp.take_along_axis(lg, tokens[:, None], axis=1)[:, 0]
    return (jnp.max(lg, axis=1) - chosen) / jnp.std(lg, axis=1)


def gaps(ref_logits, tokens) -> np.ndarray:
    """Per position: (reference's best logit - reference's logit of
    ``tokens[i]``) / standard deviation of that position's logits.
    Computed over every row of ``ref_logits`` (one shape a cell, so
    one program whatever the answers' lengths) and cut to the tokens
    given."""
    import jax
    import jax.numpy as jnp
    tokens = np.asarray(tokens)
    padded = np.zeros(ref_logits.shape[0], np.int32)
    padded[:len(tokens)] = tokens
    out = jax.jit(_gap_rows)(ref_logits, jnp.asarray(padded))
    return np.asarray(out, np.float64)[:len(tokens)]


def numbers_of(all_gaps: Sequence[np.ndarray]) -> Dict[str, float]:
    g = np.concatenate(list(all_gaps)) if len(all_gaps) else np.zeros(0)
    if g.size == 0 or not np.isfinite(g).all():
        return {k: float("inf") for k in NUMBERS}
    return {"served_gap_max": float(g.max()),
            "served_gap_mean": float(g.mean())}


def score(reference, dims: dict, seed: int, sample: Sequence[dict],
          seq_pad: int, out_pad: int, control: bool = False) -> dict:
    """Run the reference over the sample.  Returns the program's
    numbers and, with ``control``, the float8 control's beside them."""
    import jax.numpy as jnp
    prog, ctrl, n_tokens = [], [], 0
    for r in sample:
        served = np.asarray(r["tokens"], np.int64)
        seq = np.zeros(seq_pad, np.int64)
        full = np.concatenate([np.asarray(r["prompt"], np.int64), served])
        seq[:len(full)] = full
        first = r["prompt_len"] - 1
        ref = reference.logits_at(dims, seed, seq, first, out_pad)
        prog.append(gaps(ref, served))
        n_tokens += len(served)
        if control:
            low = reference.logits_at(dims, seed, seq, first, out_pad,
                                      precision="fp8")
            first_low = np.asarray(jnp.argmax(low, axis=1))[:len(served)]
            ctrl.append(gaps(ref, first_low))
            del low
        del ref
    out = {"requests": len(sample), "tokens": n_tokens,
           "program": numbers_of(prog)}
    if control:
        out["control"] = numbers_of(ctrl)
    return out


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, lines): every number within its limit; one line for
    each number compared, beside its limit."""
    lines, ok = [], True
    for name in NUMBERS:
        value, limit = numbers[name], float(limits[name])
        within = bool(value <= limit)
        ok &= within
        lines.append({"compared": name, "value": value, "limit": limit,
                      "within": within})
    return ok, lines
