"""The comparison that decides ``correct``.

Three numbers, each beside its limit:

  served_gap_max   Over a sample of the requests that the window finished
                   (the longest among them, the rest drawn from the seed):
                   the reference is run once over each prompt with its
                   served tokens, and at each served token's position the
                   gap by which that token's reference logit lies below
                   the reference's best is taken. The widest gap. Greedy
                   decoding serves the top logit of the program's own
                   bf16 arithmetic, so only near-ties may differ.
  ledger_gap       Sum over tenants of |billed - served|: the scheduler's
                   ledger against prompt + generated tokens of every
                   request it admitted. Exact: limit 0.
  lost             Requests that lost or gained tokens: a finished
                   request without exactly the tokens it asked for, an
                   admitted one with none, or a submitted one that is
                   neither queued nor admitted. Exact: limit 0.

The control (``control_gap_max``) is the reference computed in float8 in
the program's place: at each position of the same sequences, the gap of
the token it puts first. It runs only when asked for (``--control 1``),
and then it is the number held to the limit in the served tokens' place,
so that a control run comes out not correct.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

SAMPLE_TOKENS = 400      # served tokens the sample reaches at least
SAMPLE_MIN = 4           # requests at least (other slots, other lengths)
SAMPLE_MAX = 12          # requests at most


def sample(finished: List, seed: int) -> List:
    """The longest finished request, then others in an order drawn from
    the seed, until both SAMPLE_TOKENS served tokens and SAMPLE_MIN
    requests are reached, or SAMPLE_MAX requests."""
    if not finished:
        return []
    by_len = sorted(finished, key=lambda r: (-(r.prompt_len + r.out_len),
                                             r.rid))
    rest = by_len[1:]
    order = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7]) \
        .permutation(len(rest))
    out = [by_len[0]]
    toks = len(by_len[0].served.generated)
    for i in order:
        if (toks >= SAMPLE_TOKENS and len(out) >= SAMPLE_MIN) or \
                len(out) >= SAMPLE_MAX:
            break
        out.append(rest[i])
        toks += len(rest[i].served.generated)
    return out


def ledger(submitted: List, sched) -> Dict[str, int]:
    """Billing and token conservation over every request of the run."""
    want: Dict[int, int] = {}
    lost = 0
    queued = {id(r) for q in sched.queues.values() for r in q}
    for req in submitted:
        r = req.served
        admitted = req.picked is not None
        if admitted:
            want[r.tenant_id] = want.get(r.tenant_id, 0) + \
                len(r.prompt) + len(r.generated)
            done = r.finish_time >= 0
            if (done and len(r.generated) != r.max_new_tokens) or \
                    not r.generated or len(r.generated) > r.max_new_tokens:
                lost += 1
        elif id(r) not in queued:
            lost += 1
    gap = sum(abs(sched.served_tokens.get(t, 0) - n)
              for t, n in want.items())
    gap += sum(n for t, n in sched.served_tokens.items() if t not in want)
    return {"ledger_gap": int(gap), "lost": int(lost)}


def gaps(ref, w, m: Dict, reqs: List, pad_to: int, control: bool = False
         ) -> Dict[str, float]:
    """Widest gap of the served tokens (and, with ``control``, of the
    float8 reference's first tokens) below the reference's best logit."""
    import jax.numpy as jnp
    n_tok, n_equal = 0, 0
    per_request, ctrl_per_request = [], []
    for req in reqs:
        r = req.served
        p, g = len(r.prompt), len(r.generated)
        seq = np.zeros(pad_to, np.int32)
        full = list(r.prompt) + list(r.generated[:-1])
        seq[:len(full)] = full
        # position p - 1 + j predicts generated token j
        served = np.zeros(pad_to, np.int32)
        served[p - 1:p - 1 + g] = r.generated
        probes = [served]
        if control:
            _, c_first, _ = ref.stats(w, m, jnp.asarray(seq),
                                      jnp.asarray(served[None]), quant="fp8")
            probes.append(np.asarray(c_first))
        top, first, at = (np.asarray(a) for a in ref.stats(
            w, m, jnp.asarray(seq), jnp.asarray(np.stack(probes))))
        sl = slice(p - 1, p - 1 + g)
        per_request.append(float(np.max(top[sl] - at[0, sl])))
        n_tok += g
        n_equal += int(np.sum(first[sl] == served[sl]))
        if control:
            ctrl_per_request.append(float(np.max(top[sl] - at[1, sl])))
    out = {"served_gap_max": max(per_request, default=0.0),
           "per_request": per_request, "tokens": n_tok, "equal": n_equal}
    if control:
        out["control_gap_max"] = max(ctrl_per_request, default=0.0)
        out["control_per_request"] = ctrl_per_request
    return out
