"""What `correct` rests on in a serving cell: the check streams against the
configuration's own plain reference (`references/<name>.py`, which
manifest.load_reference loads), whatever the architecture.

  the program's own logits         where the program hands out logits (the
      prefill program of a prompt's bucket, by default), they are held to the
      reference's logits that chose the same tokens: the reference's
      LOGIT_TOL, over rows x vocabulary logits.
  the served tokens                every token of the check streams.  They
      were served through the normal path, several streams at once, so the
      batch programs ran at batch > 1 with unequal row positions and pads.
      The path hands out tokens, not logits, so the reference says, for each
      served token, the logits that chose it, and the harness how far below
      the reference's own best logit the served token lies (its regret).  The
      largest regret and the mean regret each have a bound of their own
      (REGRET_MAX_TOL, REGRET_MEAN_TOL), which the reference's file states
      with its reasons.
  the architecture's mechanism     what the logits cannot see (a layer whose
      result enters the stream under the noise of everything else): the
      reference's `mechanism_checks` compares it by itself and states each
      tolerance; the harness holds every entry to it.

What depends on the architecture is the reference's to say, through three
optional names (references/__init__.py): which logits chose each served token
(`chosen_logits`), what the program's own compiled path gives outside the
served stream (`program_logits`), and `mechanism_checks`.  A reference that
brings none of them is checked by the defaults below.  The reference supplies
logits and errors; every regret, every comparison with a tolerance and the
verdict are computed here.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Tuple

import jax.numpy as jnp
import numpy as np

from . import manifest


def default_chosen_logits(ref, length: int) -> Callable:
    """Where every step yields each stream's next token from the logits at its
    last position: the stream's prompt + served[:-1] through `ref.forward`
    once, padded on the right to `length` (earlier positions do not see what
    follows, and one length is one compilation); row i is the row that chose
    served[i]."""

    def chosen_logits(cb, stream) -> np.ndarray:
        n, served = len(stream["prompt_ids"]), stream["served"]
        full = np.asarray(stream["prompt_ids"] + served[:-1], np.int32)
        want = np.asarray(ref.forward(cb.params, np.pad(full, (0, length - len(full))), cb.cfg))
        return want[n - 1: n - 1 + len(served)]

    return chosen_logits


def default_program_logits(cb, stream) -> Tuple[np.ndarray, List[int]]:
    """The batcher's prefill program of the prompt's bucket, called as
    `_admit_full_prefill` calls it (left pads, their count): its logits are
    held to row 0, the row that chose the first served token."""
    from cluster_anywhere_tpu.models.generate import prefill

    prompt = np.asarray(stream["prompt_ids"], np.int32)
    n = len(prompt)
    bucket = cb._bucket(n, len(stream["served"]))
    padded = np.zeros(bucket, np.int32)
    padded[bucket - n:] = prompt
    logits, _ = prefill(cb.params, jnp.asarray(padded[None]), cb.cfg, cb.t_max,
                        pad=jnp.asarray([bucket - n], np.int32))
    return np.asarray(logits, np.float32), [0]


def check_serving(cb, streams: List[Dict[str, Any]], ref) -> Dict[str, Any]:
    """`streams`: [{"prompt_ids", "served", "request_id"?}, ...], each
    answered through the serving path while the others were (`request_id`:
    the batcher's own id of the stream, for a reference whose generation its
    tokens do not determine); `ref`: the configuration's reference module.
    `chosen_logits` and `program_logits` are asked stream by stream, then
    `mechanism_checks` once."""
    length = max(len(s["prompt_ids"]) + len(s["served"]) - 1 for s in streams)
    chosen_logits, program_logits, mechanism_checks = (
        getattr(ref, name, None) for name in manifest.REFERENCE_OPTIONAL)
    chosen_logits = chosen_logits or default_chosen_logits(ref, length)
    program_logits = program_logits or default_program_logits
    logit_err, regrets, agree = 0.0, [], 0
    for s in streams:
        served = [int(t) for t in s["served"]]
        want = np.asarray(chosen_logits(cb, s), np.float32)
        if want.shape[0] != len(served):
            raise ValueError(f"{want.shape[0]} rows of logits for {len(served)} served tokens")
        logits, rows = program_logits(cb, s)
        logit_err = max(logit_err, float(np.max(np.abs(np.asarray(logits, np.float32) - want[rows]))))
        regret = want.max(axis=-1) - want[np.arange(len(served)), served]
        regrets.extend(float(r) for r in regret)
        agree += int(np.sum(regret == 0.0))
    report = {
        "logit_max_abs_err": logit_err, "logit_tolerance": ref.LOGIT_TOL,
        "regret_max": max(regrets), "regret_max_tolerance": ref.REGRET_MAX_TOL,
        "regret_mean": sum(regrets) / len(regrets), "regret_mean_tolerance": ref.REGRET_MEAN_TOL,
        "agree_share": agree / len(regrets), "streams": len(streams), "positions": len(regrets),
    }
    ok = (logit_err <= ref.LOGIT_TOL and report["regret_max"] <= ref.REGRET_MAX_TOL
          and report["regret_mean"] <= ref.REGRET_MEAN_TOL)
    if mechanism_checks is not None:
        report["mechanism"] = [
            {"name": str(m["name"]), "error": float(m["error"]), "tolerance": float(m["tolerance"]),
             "why": str(m["why"])}
            for m in mechanism_checks(cb, streams)
        ]
        ok = ok and all(m["error"] <= m["tolerance"] for m in report["mechanism"])
    report["ok"] = bool(ok)
    return report
