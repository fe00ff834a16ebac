"""What decides ``correct``: the window's served logits and tokens against
the plain reference.

Once the window has closed and the program's device state is freed, a
sample of the requests completed in the window, drawn from the seed, is run
through the float32 reference: each request's domain prompt under its domain
expert, and its verifier prompt (which holds the served domain token) under
the verifier. Three numbers are compared:

- ``logit_err``: the widest gap between a served last-position logit and
  the reference's, over every vocabulary entry of every sampled stage. The
  program's bfloat16 rounding reads a few tenths of a logit at most; a stage
  served by the wrong or a stale expert, a skipped layer, or weights in a
  lower precision read more. Its limit is the configuration's
  ``check.logit_err``, set from chip readings of the program and of the
  control (``PERF.md``).
- ``logit_gap``: the widest gap by which a served token's reference logit
  lies below the reference's best. A greedy token of logits within
  ``logit_err`` of the reference's lies at most twice that below the best,
  so its limit is twice ``logit_err``'s. It catches a token altered after
  the logits were made.
- ``chain_errors``: completed requests whose stages were not exactly their
  domain expert, then the verifier (limit 0).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

from bench.lib.serve import VERIFIER
from bench.lib.traffic import rng_for


@dataclasses.dataclass
class Stage:
    expert: int               # index of the expert in the catalog
    prompt: np.ndarray
    token: int                # the token the program served
    logits: np.ndarray        # the last-position logits it served


def sample_stages(completed: List[dict], domains: List[str], n: int,
                  seed: int) -> List[Stage]:
    """The stages of ``n`` requests drawn from the seed."""
    ids = domains + [VERIFIER]
    take = min(n, len(completed))
    pick = np.sort(rng_for(seed, stream=2).choice(len(completed), take,
                                                  replace=False))
    out = []
    for i in pick:
        data = completed[i]
        for eid in (data["domain"], VERIFIER):
            if eid in data["served"]:      # a skipped stage is a chain error
                out.append(Stage(ids.index(eid), data["inputs"][eid],
                                 int(data["served"][eid]),
                                 np.asarray(data["logits"][eid])))
    return out


def chain_errors(completed: List[dict]) -> int:
    return sum(d["stages"] != [d["domain"], VERIFIER] for d in completed)


def reference_logits(reference, cfg: dict, seed: int, stages: List[Stage],
                     precision: str = "float32") -> List[np.ndarray]:
    """The reference's last-position logits of every stage, one expert at a
    time."""
    out: Dict[int, np.ndarray] = {}
    for expert in sorted({st.expert for st in stages}):
        rows = [i for i, st in enumerate(stages) if st.expert == expert]
        logits = reference.last_logits(
            cfg, seed, expert, np.stack([stages[i].prompt for i in rows]),
            precision=precision)
        out.update(zip(rows, logits))
    return [out[i] for i in range(len(stages))]


def logit_err(ref: List[np.ndarray], got: List[np.ndarray]) -> float:
    """The widest |served - reference| logit over all rows."""
    return max((float(np.max(np.abs(g - r))) for r, g in zip(ref, got)),
               default=float("nan"))


def token_gaps(ref: List[np.ndarray], tokens: List[int]) -> np.ndarray:
    """Reference best logit minus the reference logit of each token."""
    return np.asarray([float(r.max() - r[t]) for r, t in zip(ref, tokens)])


def checks(cfg: dict, stages: List[Stage], ref: List[np.ndarray],
           n_chain_errors: int) -> dict:
    """Each number compared, with its limit and which side of it passes."""
    limit = cfg["check"]["logit_err"]
    gaps = token_gaps(ref, [st.token for st in stages])
    return {
        "logit_err": {"value": logit_err(ref, [st.logits for st in stages]),
                      "limit": limit, "pass": "<="},
        "logit_gap": {"value": float(gaps.max()) if gaps.size else None,
                      "limit": 2 * limit, "pass": "<="},
        "chain_errors": {"value": n_chain_errors, "limit": 0, "pass": "<="},
        "tokens_compared": {"value": len(stages), "limit": 2, "pass": ">="},
    }


def failed_stages(stages: List[Stage], ref: List[np.ndarray],
                  c: dict) -> int:
    """Sampled stages outside a limit."""
    return sum(float(np.max(np.abs(st.logits - r))) > c["logit_err"]["limit"]
               or float(r.max() - r[st.token]) > c["logit_gap"]["limit"]
               for st, r in zip(stages, ref))


def passed(c: dict) -> bool:
    def ok(v):
        if v["value"] is None or v["value"] != v["value"]:     # None, NaN
            return False
        return v["value"] <= v["limit"] if v["pass"] == "<=" \
            else v["value"] >= v["limit"]
    return all(ok(v) for v in c.values())
