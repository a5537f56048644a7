"""A toy of a generation that is not one token a step, which a test adds to a
copy of benchmarks/ as `references/toy_blocks.py`: an answer is made a block of
`cfg.block` positions at a time.  A block starts as mask tokens; one pass sees
every earlier block and the whole of its own block, in both directions; the
logits at a masked position itself give its token and a confidence; the most
confident masked positions are fixed, the others stay masked for the next
pass.  So the logits that chose a token depend on which positions of its block
were fixed before it, which the served tokens do not say: the batcher keeps,
by request id, the pass at which each served token was fixed, and
`chosen_logits` replays the passes from that record.  One attention layer over
learned positions, plain jax.numpy at float32.  It is not under benchmarks/:
the benchmark runs no such configuration."""
import jax
import jax.numpy as jnp
import numpy as np

SCOPES, KERNELS = (), ()
# float32 on both sides: what is left is the order of summation
LOGIT_TOL, REGRET_MAX_TOL, REGRET_MEAN_TOL, LOSS_TOL = 2e-3, 1e-3, 1e-4, 1e-3


def program_config(config_file, **extra):
    c = config_file["config"]
    return dict(d_model=c["hidden_size"], vocab_size=c["vocab_size"], block=c["block_length"],
                max_seq_len=c["max_position_embeddings"], **extra)


def forward(params, ids, cfg):
    """ids: [T] -> logits [T, V].  Position i sees position j where j's block
    is not after i's."""
    ids = jnp.asarray(ids)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][ids] + params["pos"][: len(ids)]
        blocks = jnp.arange(len(ids)) // cfg.block
        s = (x @ params["wq"]) @ (x @ params["wk"]).T * x.shape[-1] ** -0.5
        s = jnp.where(blocks[None, :] <= blocks[:, None], s, -jnp.inf)
        x = x + jax.nn.softmax(s, axis=-1) @ (x @ params["wv"])
        return x @ params["head"]


def loss(params, ids, cfg):
    raise NotImplementedError("the toy is served, not trained")


def param_count(c):
    e = c["hidden_size"]
    return 2 * c["vocab_size"] * e + c["max_position_embeddings"] * e + 3 * e * e


def train_flops_per_step(c, batch, seq):
    return 3.0 * batch * seq * 2 * (param_count(c) - c["vocab_size"] * c["hidden_size"])


def decode_step_bytes(c, slots, t_max, bytes_per=4):
    return param_count(c) * bytes_per


# -- what the check asks of a generation that is not one causal token a step -----


def _block_passes(cb, stream):
    """[(the block's first position, its ids before the answer's part is
    known: prompt tokens and masks, [(position, pass, served index)])] for each
    block that holds part of the answer, from the batcher's record."""
    prompt, served = stream["prompt_ids"], stream["served"]
    fixed_at = cb.fixed_at(stream["request_id"])  # served token i was fixed in this pass of its block
    if len(fixed_at) != len(served):
        raise ValueError(f"a record of {len(fixed_at)} passes for {len(served)} served tokens")
    n, b, out = len(prompt), cb.cfg.block, []
    for start in range(n - n % b, n + len(served), b):
        end = min(start + b, n + len(served))
        ids = [prompt[i] if i < n else cb.cfg.mask_id for i in range(start, end)]
        out.append((start, ids, [(i, fixed_at[i - n], i - n) for i in range(max(start, n), end)]))
    return out


def chosen_logits(cb, stream):
    """Replays each block's passes: before pass p the positions fixed in
    earlier passes hold their served tokens and the others the mask; a
    position fixed in pass p takes its row from that pass."""
    known = list(stream["prompt_ids"]) + list(stream["served"])
    rows = [None] * len(stream["served"])
    for start, ids, answer in _block_passes(cb, stream):
        for p in sorted({q for _, q, _ in answer}):
            state = list(ids)
            for i, q, _ in answer:
                if q < p:
                    state[i - start] = known[i]
            logits = np.asarray(forward(cb.params, known[:start] + state, cb.cfg))
            for i, q, j in answer:
                if q == p:
                    rows[j] = logits[i]
    return np.stack(rows)


def program_logits(cb, stream):
    """The program's own first pass over the first block of the answer, held
    to the rows of the tokens that pass fixed."""
    start, ids, answer = _block_passes(cb, stream)[0]
    logits = cb.first_pass_logits(stream["prompt_ids"][:start] + ids)
    first = [(i, j) for i, q, j in answer if q == 0]
    return np.stack([logits[i] for i, _ in first]), [j for _, j in first]
