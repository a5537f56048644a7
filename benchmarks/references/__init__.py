"""One file an architecture: its plain mathematics and everything else the
harness has to know about it.  A configuration (`configs/<config>.json`) names
its file here under `"reference"`; `harness/manifest.load_reference` loads it
by path, as a per-layer metric's reader is loaded, so a later PR brings an
architecture by adding a file and nothing in `harness/` names one.

What `references/<name>.py` has (manifest.REFERENCE_INTERFACE lists the names,
and `load_reference` refuses a file that lacks one):

  program_config(config_file, **extra) -> dict
      The program's `TransformerConfig` fields from the configuration file's
      published keys (`config_file["config"]`); `extra` overrides them.
  forward(params, ids, cfg) -> logits [T, V]
  loss(params, ids, cfg) -> float
      One sequence `ids` [T] through the architecture in float32 at `highest`
      matmul precision: straightforward `jax.numpy`, no kernel, no cache, no
      batching, no code of `cluster_anywhere_tpu/models/`.  `params` is the
      parameter tree the program made from the seed, `cfg` the program's
      configuration object (read for sizes only).  `loss` is the mean
      next-token cross entropy of ids[:-1] -> ids[1:].
  SCOPES, KERNELS
      The `jax.named_scope` names and Pallas kernel names this architecture's
      programs write beyond the common ones (program_trace.SCOPES, .KERNELS);
      the per-layer readers know an operation by them.
  param_count(c) -> int
  train_flops_per_step(c, batch, seq) -> float
  decode_step_bytes(c, slots, t_max, bytes_per=2) -> int
      Parameters, the operations a training step requires, and the bytes a
      decode step has to read, from the published keys `c`.
  LOGIT_TOL, REGRET_MAX_TOL, REGRET_MEAN_TOL, LOSS_TOL
      What `correct` allows between the program and this reference, each with
      its reason and the chip readings it was set from beside it.

What it may have besides (manifest.REFERENCE_OPTIONAL; a file that lacks them
loads, and `harness/reference.py` `check_serving` checks it by the default).
They are the parts of a serving cell's check that depend on the architecture.
`cb` is the replica's batcher (its `params`, `cfg` and whatever else this
file knows of its own program); a `stream` is one check stream,
{"prompt_ids", "served", "request_id"}: the prompt, the tokens that were
served for it while the other check streams were served, and the batcher's
own id of that request.  A reference hands back logits and errors, never a
verdict: the harness computes every regret, compares with the tolerances this
file states, and decides.

  chosen_logits(cb, stream) -> float32 [len(served), V]
      This reference's logits that chose each served token: row i is the row
      whose largest entry the reference would have served as served[i].
      Default: every step yields each stream's one next token from the logits
      at its last position, so prompt + served[:-1] goes through `forward`
      once, and row i is position len(prompt) - 1 + i.
  program_logits(cb, stream) -> (float32 [k, V], rows)
      Logits the program's own compiled path gives for this stream's prompt
      outside the served stream, and the k rows of `chosen_logits` they are
      held to by LOGIT_TOL.  Default: the batcher's prefill program of the
      prompt's bucket, left-padded as an admit pads it, held to row 0.
  mechanism_checks(cb, streams) -> [{"name", "error", "tolerance", "why"}]
      Outputs of the architecture's own mechanism that the logits cannot see,
      each compared with the plain reference here, each tolerance stated here
      with its reason and the chip readings it was set from.  The result line
      carries every entry under `check.mechanism`, and one `error` over its
      `tolerance` is not correct.  Default: none.  It is called once, after
      `chosen_logits` has been asked for every stream, so a file that brings
      both may keep what the one pass computed for the other (and the default's
      pass as its own `chosen_logits`, for that alone).  It enters the program
      where the window's compiled programs do, or as near below them as the
      program lets it, with their shapes: a compiled program that hands out
      logits and tokens hands out no layer's result.

A generation that is not one token a step from the last position's logits (a
step that yields several tokens or none, logits taken at the position itself,
a choice that depends on what else of the answer was known at that step)
brings `chosen_logits`, `program_logits` and a record its batcher keeps by
request id of what the tokens do not determine (for each served token, the
step at which it was fixed): `chosen_logits` replays the steps from that
record, asked of `cb` under `stream["request_id"]` by whatever name that
program gives it, and `program_logits` holds the first step's logits.
"""
