"""Batch LLM inference pipeline (analogue of the reference's
python/ray/llm/_internal/batch/processor/ + stages/: chat template ->
tokenize -> inference -> detokenize, composed as Data map stages).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ..util import tracing


class ByteTokenizer:
    """Offline byte-level tokenizer (ids: 0=pad, 1=bos, 2=eos, byte b -> b+3).
    Stands in for HF tokenizers in air-gapped environments; any object with
    encode/decode can be plugged into ProcessorConfig.tokenizer."""

    vocab_size = 259
    pad_id, bos_id, eos_id = 0, 1, 2

    def encode(self, text: str, add_bos: bool = True) -> List[int]:
        ids = [b + 3 for b in text.encode("utf-8")]
        return ([self.bos_id] + ids) if add_bos else ids

    def decode(self, ids) -> str:
        out = bytearray()
        for i in ids:
            i = int(i)
            if i >= 3:
                out.append(i - 3)
        return out.decode("utf-8", "replace")


@dataclass
class ModelSpec:
    """Which flagship-transformer weights to run. Presets init random weights
    deterministically (seed) — checkpoint loading goes through `params_path`
    (an orbax/np.savez dir produced by train).  "custom" takes every width
    from `config_overrides` (TransformerConfig's defaults for the rest)."""

    preset: str = "tiny"  # tiny | small | custom
    params_path: Optional[str] = None
    seed: int = 0
    config_overrides: Dict[str, Any] = field(default_factory=dict)

    def transformer_config(self, vocab_size: int):
        from ..models.transformer import TransformerConfig

        presets = {
            "tiny": dict(d_model=64, n_layers=2, n_heads=4, n_kv_heads=4, d_head=16, d_ff=128),
            "small": dict(d_model=256, n_layers=4, n_heads=8, n_kv_heads=8, d_head=32, d_ff=512),
            "custom": {},
        }
        if self.preset not in presets:
            raise ValueError(
                f"unknown model preset {self.preset!r}: one of {sorted(presets)}"
            )
        base = presets[self.preset]
        base.update(self.config_overrides)
        return TransformerConfig(vocab_size=vocab_size, **base)


@dataclass
class ProcessorConfig:
    model: ModelSpec = field(default_factory=ModelSpec)
    tokenizer: Any = None  # defaults to ByteTokenizer
    batch_size: int = 8
    concurrency: int = 1
    max_prompt_len: int = 64
    max_new_tokens: int = 16
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    apply_chat_template: bool = False
    system_prompt: str = ""
    # prefix/KV-cache reuse in ContinuousLLMServer: requests sharing a
    # system-prompt prefix skip its prefill (0 entries disables)
    prefix_cache_entries: int = 8
    prefix_block: int = 16


def engine_placement(params) -> Dict[str, Any]:
    """The devices that hold `params`: {"platform", "device_kind", "count"}.
    Raises on a TPU host whose engine landed on the CPU: a replica that asked
    for no TPU (num_tpus=0) runs in the CPU worker pool, which the head pins
    with JAX_PLATFORMS=cpu, and would otherwise serve the model from the host
    CPU without a word."""
    import jax

    from ..core import accelerators

    devices = {d for x in jax.tree_util.tree_leaves(params) for d in x.devices()}
    first = min(devices, key=lambda d: d.id)
    chips = accelerators.num_tpu_chips()
    if chips and first.platform != "tpu":
        raise RuntimeError(
            f"this host has {chips} TPU chip(s) but the model's parameters are "
            f"on {first.platform!r}: the engine runs in a worker without the "
            "accelerator.  Ask for a chip (num_tpus=1 in "
            "build_llm_deployment / build_continuous_llm_deployment, or "
            "ray_actor_options of your own deployment) so it is placed in the "
            "TPU worker pool."
        )
    return {
        "platform": first.platform,
        "device_kind": first.device_kind,
        "count": len(devices),
    }


def model_params(model: ModelSpec, tcfg):
    """The model's weights on the device: read from `model.params_path` where
    there is one, else made from `model.seed`.  Span `llm.replica.init.params`
    (`source`: `path` or `seed`) holds the work and not its dispatch: the
    weights are there when it ends."""
    import jax

    with tracing.span("llm.replica.init.params", source="path" if model.params_path else "seed"):
        if model.params_path:
            from . import _params_io

            params = _params_io.load_params(model.params_path)
        else:
            from ..models.transformer import init_params

            params = init_params(jax.random.key(model.seed), tcfg)
        return jax.block_until_ready(params)


class _InferenceWorker:
    """Actor-pool UDF: holds compiled model + params for its lifetime
    (reference: stages run in vLLM engine actors)."""

    def __init__(self, cfg: ProcessorConfig):
        self.cfg = cfg
        self.tok = cfg.tokenizer or ByteTokenizer()
        self.tcfg = cfg.model.transformer_config(self.tok.vocab_size)
        self.params = model_params(cfg.model, self.tcfg)
        engine_placement(self.params)
        self._step = 0

    def __call__(
        self,
        batch: Dict[str, np.ndarray],
        max_new_tokens: Optional[int] = None,
        temperature: Optional[float] = None,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
    ) -> Dict[str, np.ndarray]:
        import jax
        import jax.numpy as jnp

        from ..models.generate import generate

        cfg = self.cfg
        max_new_tokens = cfg.max_new_tokens if max_new_tokens is None else max_new_tokens
        temperature = cfg.temperature if temperature is None else temperature
        top_k = cfg.top_k if top_k is None else top_k
        top_p = getattr(cfg, "top_p", 1.0) if top_p is None else top_p
        prompts = [str(p) for p in batch["prompt"].tolist()]
        encoded = [self.tok.encode(p)[: cfg.max_prompt_len] for p in prompts]
        # left-pad to the FIXED max_prompt_len so every batch hits the same
        # compiled program (per-batch max length would recompile per shape)
        max_len = cfg.max_prompt_len
        ids = np.full((len(encoded), max_len), self.tok.pad_id, np.int32)
        lens = np.empty(len(encoded), np.int32)
        for i, e in enumerate(encoded):
            ids[i, max_len - len(e):] = e
            lens[i] = len(e)
        self._step += 1
        out = generate(
            self.params,
            jnp.asarray(ids),
            jax.random.key(cfg.model.seed * 1000003 + self._step),
            cfg=self.tcfg,
            max_new_tokens=max_new_tokens,
            temperature=temperature,
            top_k=top_k,
            top_p=top_p,
            prompt_lens=jnp.asarray(lens),
        )
        out = np.asarray(out)
        texts = [self.tok.decode(row) for row in out]
        result = dict(batch)
        result["generated_tokens"] = out
        result["generated_text"] = np.asarray(texts, dtype=object)
        return result

    def stream(
        self,
        prompt: str,
        max_new_tokens: Optional[int] = None,
        temperature: Optional[float] = None,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
    ):
        """Token-by-token decoding of one prompt; a generator meant to run as
        a num_returns="streaming" actor call, so clients receive tokens as
        they are sampled (the streaming-decode path of the reference's serve
        LLM engines)."""
        import jax
        import jax.numpy as jnp

        from ..models.generate import stream_generate

        cfg = self.cfg
        max_new_tokens = cfg.max_new_tokens if max_new_tokens is None else max_new_tokens
        encoded = self.tok.encode(prompt)[: cfg.max_prompt_len]
        ids = np.full((1, cfg.max_prompt_len), self.tok.pad_id, np.int32)
        ids[0, cfg.max_prompt_len - len(encoded):] = encoded
        self._step += 1
        for tok in stream_generate(
            self.params,
            jnp.asarray(ids),
            jax.random.key(cfg.model.seed * 1000003 + self._step),
            cfg=self.tcfg,
            max_new_tokens=max_new_tokens,
            temperature=cfg.temperature if temperature is None else temperature,
            top_k=cfg.top_k if top_k is None else top_k,
            top_p=getattr(cfg, "top_p", 1.0) if top_p is None else top_p,
            prompt_lens=jnp.asarray([len(encoded)], np.int32),
        ):
            tid = int(tok[0])
            yield {"token_id": tid, "text": self.tok.decode([tid])}


class Processor:
    """Callable dataset -> dataset pipeline."""

    def __init__(
        self,
        config: ProcessorConfig,
        preprocess: Optional[Callable[[dict], dict]] = None,
        postprocess: Optional[Callable[[dict], dict]] = None,
    ):
        self.config = config
        self.preprocess = preprocess
        self.postprocess = postprocess

    def __call__(self, dataset):
        cfg = self.config
        ds = dataset
        if self.preprocess is not None:
            ds = ds.map(self.preprocess)
        if cfg.apply_chat_template:
            system = cfg.system_prompt

            def template(row):
                prompt = row.get("prompt", "") if isinstance(row, dict) else str(row)
                msgs = row.get("messages") if isinstance(row, dict) else None
                if msgs:
                    text = "".join(
                        f"<|{m['role']}|>{m['content']}" for m in msgs
                    ) + "<|assistant|>"
                else:
                    text = (f"<|system|>{system}" if system else "") + f"<|user|>{prompt}<|assistant|>"
                out = dict(row)
                out["prompt"] = text
                return out

            ds = ds.map(template)
        ds = ds.map_batches(
            _InferenceWorker,
            fn_constructor_args=(cfg,),
            batch_size=cfg.batch_size,
            concurrency=cfg.concurrency,
            batch_format="numpy",
        )
        if self.postprocess is not None:
            ds = ds.map(self.postprocess)
        return ds


def build_llm_processor(
    config: ProcessorConfig,
    preprocess: Optional[Callable[[dict], dict]] = None,
    postprocess: Optional[Callable[[dict], dict]] = None,
) -> Processor:
    return Processor(config, preprocess, postprocess)
