"""LLMServer — the serve-deployment face of the engine.

One replica hosts one LLMEngine; the serve layer (controller, handles,
proxies) sees an ordinary user callable:

    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMServer

    app = serve.deployment(num_replicas=1)(LLMServer).bind(
        model="gpt-tiny", engine_config={"max_batch": 8})
    handle = serve.run(app)
    # full completion
    out = ray_tpu.get(handle.remote({"tokens": [1, 5, 9],
                                     "max_tokens": 16}), timeout=60)
    # token streaming (handle async-iterates too; proxies speak
    # NDJSON or SSE — ?stream=1 / ?stream=sse)
    for tok in handle.options(stream=True).remote(
            {"tokens": [1, 5, 9], "max_tokens": 16, "stream": True}): ...

`queue_len()` reports the engine's waiting+running depth; the replica
ships it in its health ping so the controller's request-based autoscaler
scales on engine backlog, not just in-flight RPCs (controller.py).

Tensor parallelism: ``engine_config={"tp": N}`` makes this replica span
an N-chip mesh — prefill/decode lower sharded (heads/FFN on ``tp``, KV
pool block-sharded per chip; docs/SHARDING.md) while the serve layer
still sees one replica actor. ``stats()`` then carries
``kv_blocks_per_chip`` / ``kv_bytes_per_chip``.
"""
from __future__ import annotations

import time
from typing import Any, Dict, Optional, Tuple

from .engine import EngineConfig, LLMEngine


def build_model(model: Any = "gpt-tiny", seed: int = 0,
                tp: int = 1) -> Tuple[Any, Any]:
    """-> (model, params). `model` is a registry name ("gpt-tiny",
    "llama-tiny", "gpt2-small", "llama2-7b"), or a dict
    {"family": "gpt"|"llama", **config_kwargs} for explicit sizing.
    Params initialize from `seed` so disaggregated stages agree. With
    ``tp > 1`` they are born sharded on the tp mesh the engine will
    build over the same devices: a model that only fits across chips
    (llama2-7b is 27 GB of float32) never sits whole on the first."""
    import jax
    import jax.numpy as jnp

    from ...models import GPT, GPTConfig, Llama, LlamaConfig

    if isinstance(model, str):
        registry = {
            "gpt-tiny": ("gpt", dict(dtype=jnp.float32, use_flash=False)),
            "llama-tiny": ("llama", dict(dtype=jnp.float32,
                                         use_flash=False)),
            "gpt2-small": ("gpt", dict(preset="small")),
            "llama2-7b": ("llama", dict(preset="llama2_7b")),
        }
        if model not in registry:
            raise ValueError(f"unknown model {model!r}; "
                             f"known: {sorted(registry)}")
        family, kw = registry[model]
        preset = kw.pop("preset", "tiny")
    else:
        kw = dict(model)
        family = kw.pop("family")
        preset = kw.pop("preset", "tiny")
    if family == "gpt":
        cfg = getattr(GPTConfig, preset)(**kw)
        m = GPT(cfg)
    elif family == "llama":
        cfg = getattr(LlamaConfig, preset)(**kw)
        m = Llama(cfg)
    else:
        raise ValueError(f"unknown model family {family!r}")
    if tp > 1:
        from ...parallel.sharding import MeshOwner, sharded_init

        owner = MeshOwner.tp_mesh(tp, name="llm-init")
        init = sharded_init(m.init, owner, owner.layout.param_specs(m))
    else:
        init = jax.jit(m.init)
    return m, init(jax.random.PRNGKey(seed))


class LLMServer:
    """Serve user callable wrapping an LLMEngine (wrap with
    `serve.deployment(...)(LLMServer)`)."""

    def __init__(self, model: Any = "gpt-tiny",
                 engine_config: Optional[Dict[str, Any]] = None,
                 seed: int = 0, name: str = ""):
        cfg = EngineConfig(**(engine_config or {}))
        m, params = build_model(model, seed=seed, tp=cfg.tp)
        self.engine = LLMEngine(m, params, cfg, name=name or "serve")
        self.engine.start()

    # -- request path ---------------------------------------------------------

    def __call__(self, payload: Dict[str, Any]):
        """payload: {"tokens": [ints], "max_tokens": n, "eos_id": id?,
        "stream": bool?}. stream=True returns a generator (route it with
        handle.options(stream=True) / proxy ?stream=...); otherwise the
        full completion dict."""
        if not isinstance(payload, dict) or "tokens" not in payload:
            raise ValueError("payload must be a dict with 'tokens'")
        stream = self.engine.add_request(
            payload["tokens"], int(payload.get("max_tokens", 16)),
            eos_id=payload.get("eos_id", "__default__"))
        if payload.get("stream"):
            return self._stream_tokens(stream)
        t0 = time.perf_counter()
        toks = stream.tokens()
        return {"request_id": stream.request_id, "tokens": toks,
                "finish_reason": stream.finish_reason,
                "gen_s": round(time.perf_counter() - t0, 4)}

    @staticmethod
    def _stream_tokens(stream):
        for tok in stream:
            yield tok

    # -- control plane --------------------------------------------------------

    def queue_len(self) -> int:
        """Engine backlog — shipped in the replica health ping and read
        by the controller's autoscaler (max'd with in-flight RPCs)."""
        return self.engine.queue_depth()

    def cache_stats(self) -> Dict[str, Any]:
        """Prefix-cache health (cache_hit_rate, prefix_blocks_resident,
        ...) — the replica merges this into its health ping so the
        controller and the session-aware router can prefer cache-warm
        replicas (controller.py / handle.py)."""
        return self.engine.cache_stats()

    def stats(self) -> Dict[str, Any]:
        return self.engine.stats()

    def check_health(self) -> None:
        if not self.engine.is_alive():
            raise RuntimeError("engine scheduler thread is dead")

    def __del__(self):
        try:
            self.engine.stop(timeout=2.0)
        except Exception:
            pass
