"""Batched LM inference serving (BASELINE.json #5 shape).

Replicas hold a jitted forward; `serve.batch` coalesces concurrent requests
into one XLA call — the TPU batching path. Tiny model keeps it hermetic;
swap in LLAMA2_7B + real weights for the full config.
"""

import numpy as np

import ray_tpu
from ray_tpu import serve


@serve.deployment(num_replicas=1, max_ongoing_requests=8)
class LMServer:
    def __init__(self):
        import jax

        from ray_tpu.models.transformer import TINY, forward, init_params

        self.cfg = TINY
        self.params = init_params(jax.random.PRNGKey(0), TINY)
        self._fwd = jax.jit(lambda p, t: forward(p, t, self.cfg))

    @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.02)
    def __call__(self, payloads):
        import jax.numpy as jnp

        seq = max(len(p["tokens"]) for p in payloads)
        batch = np.zeros((len(payloads), seq), np.int32)
        for i, p in enumerate(payloads):
            batch[i, : len(p["tokens"])] = p["tokens"]
        logits = self._fwd(self.params, batch)
        next_tokens = jnp.argmax(logits[:, -1], axis=-1)
        return [{"next_token": int(t)} for t in np.asarray(next_tokens)]

    def generate(self, tokens, max_new_tokens: int = 16):
        """Autoregressive completion on the KV-cache decode path
        (``models/generation.py``)."""
        from ray_tpu.models.generation import generate, make_decode_fns

        # cache the jitted (prefill, decode_step) pair per shape — without
        # this every request would recompile the decode graphs
        key = (1, len(tokens) + max_new_tokens)
        fns_cache = getattr(self, "_fns", None)
        if fns_cache is None:
            fns_cache = self._fns = {}
        if key not in fns_cache:
            fns_cache[key] = make_decode_fns(self.cfg, key[1])
        out = generate(
            self.params,
            np.asarray([tokens], np.int32),
            self.cfg,
            max_new_tokens=max_new_tokens,
            fns=fns_cache[key],
        )
        return {"tokens": np.asarray(out)[0].tolist()}


def main():
    ray_tpu.init(ignore_reinit_error=True)
    handle = serve.run(LMServer.bind(), name="lm", route_prefix="/lm")
    out = [handle.remote({"tokens": [1, 2, 3, i]}) for i in range(8)]
    print([r.result(timeout_s=120) for r in out])
    serve.shutdown()


if __name__ == "__main__":
    main()
