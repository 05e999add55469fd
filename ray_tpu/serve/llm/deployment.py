"""The LLM deployment: one :class:`InferenceEngine` per serve replica.

``LLMServer.generate`` is a generator method, so it rides every existing
streaming surface unchanged: handle ``.options(stream=True)`` iteration,
the proxy's SSE/chunked path, and websockets — with TTFT landing in the
replica's stream spans and ``ray_tpu_serve_ttft_ms`` exactly like any
other streaming deployment. Engine-side KV-exhaustion sheds raise
``DeploymentOverloadedError`` before the first token, which the serve
plane already maps to HTTP 503 + Retry-After.

Model weights are initialised from a seed inside the replica (this repo
has no checkpoint loader); pass ``params_loader`` for real weights.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Iterator, Optional, Sequence, Union

from ray_tpu.serve.llm.engine import EngineConfig, InferenceEngine

__all__ = ["LLMServer", "llm_deployment", "TINY_MODEL"]

# small-but-real geometry (GQA + swiglu exercised) usable on the CPU
# backend: tests, benches and docs all deploy this by default
TINY_MODEL: Dict[str, Any] = {
    "vocab_size": 512,
    "d_model": 64,
    "n_layers": 2,
    "n_heads": 4,
    "n_kv_heads": 2,
    "d_ff": 128,
    "max_seq_len": 256,
    "dtype": "float32",
}


def _resolve_model_cfg(model_cfg):
    """A config dataclass as it is; a dict becomes the config of the model
    kind its ``kind`` names (``models.PAGED_KINDS``), and a
    ``TransformerConfig`` where it names none."""
    from ray_tpu.models import paged_config

    if dataclasses.is_dataclass(model_cfg):
        return model_cfg
    import jax.numpy as jnp

    cfg = dict(TINY_MODEL if model_cfg is None else model_cfg)
    config = paged_config(cfg.pop("kind", None))
    if isinstance(cfg.get("dtype"), str):
        cfg["dtype"] = jnp.dtype(cfg["dtype"]).type
    return config(**cfg)


def _resolve_engine_cfg(engine_cfg):
    if engine_cfg is None:
        return EngineConfig()
    if isinstance(engine_cfg, EngineConfig):
        return engine_cfg
    return EngineConfig(**dict(engine_cfg))


class LLMServer:
    """Serve deployment class wrapping the continuous-batching engine.

    Configs arrive as plain dicts (cloudpickle-friendly across the actor
    boundary) or as the dataclasses themselves.
    """

    def __init__(
        self,
        model_cfg: Optional[Union[Dict, Any]] = None,
        engine_cfg: Optional[Union[Dict, EngineConfig]] = None,
        *,
        weight_seed: int = 0,
        deployment: str = "llm",
        params_loader: Optional[Callable[[Any], Any]] = None,
    ):
        t_init = time.time_ns()  # a start's first stamp (``looplog.LLM_START_FIELDS``)
        import jax

        from ray_tpu._private import sampler
        from ray_tpu.models import paged_model
        from ray_tpu.train.jax_utils import ensure_platform

        # before the first program is traced: the weights' jit is a start's first
        sampler.install_jax_hooks()
        ensure_platform()  # a replica that asked for a chip runs on it
        jax.local_devices()  # the backend is up, the first device in hand
        t_backend = time.time_ns()
        cfg = _resolve_model_cfg(model_cfg)
        if params_loader is not None:
            params = params_loader(cfg)
        else:
            # jitted: the eager call holds a float32 copy of every stacked
            # tensor before the cast (7.5 GB for one GPT-J-6B MLP tensor)
            init_params = paged_model(cfg).init_params
            params = jax.jit(lambda: init_params(jax.random.PRNGKey(int(weight_seed)), cfg))()
        self._engine = InferenceEngine(
            params, cfg, _resolve_engine_cfg(engine_cfg), deployment=deployment, started=(t_init, t_backend)
        )

    def generate(
        self,
        prompt: Sequence[int],
        max_new_tokens: int = 16,
        temperature: float = 0.0,
        top_k: int = 0,
        seed: int = 0,
        eos_token: Optional[int] = None,
    ) -> Iterator[int]:
        """Stream generated token ids. Admission (and therefore any
        ``DeploymentOverloadedError`` shed) happens eagerly at call time,
        before the first yield, so sheds surface as pre-first-token
        failures on every transport."""
        stream = self._engine.submit(
            prompt,
            max_new_tokens=max_new_tokens,
            temperature=temperature,
            top_k=top_k,
            seed=seed,
            eos_token=eos_token,
        )

        def _iter():
            for tok in stream:
                yield int(tok)

        return _iter()

    def __call__(
        self, prompt, max_new_tokens: int = 16, **kw
    ) -> list:
        """Unary convenience: full completion as a token list. Accepts
        either a token sequence or the HTTP-proxy JSON convention
        (``{"prompt": [...], "max_new_tokens": ..., ...}`` as one arg)."""
        if isinstance(prompt, dict):
            payload = dict(prompt)
            tokens = payload.pop("prompt")
            max_new_tokens = payload.pop("max_new_tokens", max_new_tokens)
            kw = {**payload, **kw}
            prompt = tokens
        return list(self.generate(prompt, max_new_tokens, **kw))

    def kv_stats(self) -> Dict[str, Any]:
        return self._engine.kv_stats()

    def loop_stats(self, records: int = 64) -> Dict[str, Any]:
        """The engine loop's newest step and request records and its phase
        sums (``InferenceEngine.loop_stats``): where a step's time went; and
        ``start``: where the replica's start went, by phase and by program."""
        return self._engine.loop_stats(records)

    def check_health(self) -> bool:
        if self._engine._thread is None or not self._engine._thread.is_alive():
            raise RuntimeError("inference engine loop is not running")
        return True

    def __del__(self):
        try:
            self._engine.shutdown(timeout_s=1.0)
        except Exception:
            pass


def llm_deployment(
    model_cfg: Optional[Dict] = None,
    engine_cfg: Optional[Dict] = None,
    *,
    deployment_name: str = "llm",
    **serve_options,
):
    """Bound LLM application: ``serve.run(llm_deployment(...))``.

    ``serve_options`` pass straight through to ``@serve.deployment``
    (num_replicas, max_ongoing_requests, autoscaling_config, ...).
    ``max_ongoing_requests`` defaults to the engine's admission width
    (decode slots + waiting bound) so the replica gate and the KV-aware
    admission agree about capacity.
    """
    from ray_tpu import serve

    ecfg = _resolve_engine_cfg(engine_cfg)
    serve_options.setdefault("name", deployment_name)
    serve_options.setdefault(
        "max_ongoing_requests", ecfg.max_batch + ecfg.max_waiting
    )
    dep = serve.deployment(LLMServer, **serve_options)
    return dep.bind(
        model_cfg, engine_cfg, deployment=deployment_name
    )
