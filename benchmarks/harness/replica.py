"""The deployment the serving cells put on the chip: ``LLMServer`` itself,
through the same ``serve.deployment``/``serve.run`` path as
``llm_deployment``, plus what only the process that holds the chip can do for
the benchmark: take a profiler trace, read the engine's counters, and run the
comparison with the plain reference. It changes nothing on the served path.
"""

from __future__ import annotations

import os
import time

from ray_tpu.serve.llm.deployment import LLMServer

# the control of `correct`: the reference in the program's place, in the
# precisions below bfloat16 (fp8 is the one the limits are set against)
CONTROLS = ("fp8", "int8")
LLM_METRICS = ("ray_tpu_llm_decode_step_ms", "ray_tpu_llm_tokens_total", "ray_tpu_llm_shed_total")


class CompileWatch:
    """Counts what ``jax.monitoring`` says about compilation in this
    process: requests that went to the compile cache, its hits, and backend
    compilations."""

    def __init__(self):
        import jax.monitoring

        self.counts = {"cache_requests": 0, "cache_hits": 0, "backend_compiles": 0, "traces": 0}
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.counts["cache_requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.counts["cache_hits"] += 1

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.counts["backend_compiles"] += 1
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.counts["traces"] += 1


def device_report(dev) -> dict:
    stats = [d.memory_stats() or {} for d in dev.client.local_devices()]
    return {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(dev.client.devices()),
        "memory_peak_bytes": max((s.get("peak_bytes_in_use") or 0) for s in stats),
    }


class BenchLLMServer(LLMServer):
    def __init__(self, config: dict, *, weight_seed: int, deployment: str = "llm"):
        """``config`` is the cell's configuration file: its family gives the
        model arguments, the seeded weights and the plain reference."""
        import jax

        from benchmarks import families
        from benchmarks.harness.common import Heartbeat
        from benchmarks.harness.weights import seed_words

        t_begin = time.time()
        self._watch, self._heart = CompileWatch(), Heartbeat()
        self._family = families.of(config)
        self._model = self._family.model_kwargs(config)

        def loader(cfg):
            params = jax.jit(lambda w: self._family.make_weights(w, self._model, cfg.dtype))(seed_words(weight_seed))
            jax.block_until_ready(params)
            print(f"backend and seeded weights ready {time.time() - t_begin:.2f} s after the replica's start", flush=True)
            return params

        super().__init__(self._model, dict(config["engine"]), deployment=deployment, params_loader=loader)
        print(f"engine ready {time.time() - t_begin:.2f} s after the replica's start", flush=True)
        self._trace_dir = None

    # -- counters -------------------------------------------------------

    def bench_counters(self) -> dict:
        """The engine's host-side counters as they stand, with the compile
        watch and this process's clock."""
        from ray_tpu.util import metrics

        with metrics._lock:
            local = {n: dict(metrics._local.get(n, {})) for n in LLM_METRICS}
        step = next(iter(local["ray_tpu_llm_decode_step_ms"].values()), {"count": 0, "sum": 0.0})
        tokens = local["ray_tpu_llm_tokens_total"]
        return {
            "t": time.time(),
            "decode_steps": step["count"],
            "decode_step_ms_sum": step["sum"],
            "decode_tokens": sum(v for k, v in tokens.items() if '"decode"' in k),
            "prefill_tokens": sum(v for k, v in tokens.items() if '"prefill"' in k),
            "shed": sum(local["ray_tpu_llm_shed_total"].values()),
            "compile": dict(self._watch.counts),
            "host_stalls": list(self._heart.stalls),
            "kv": self._engine.kv_stats(),
            "device": device_report(self._engine._device),
        }

    # -- trace ----------------------------------------------------------

    def bench_start_trace(self, directory: str) -> bool:
        import jax

        os.makedirs(directory, exist_ok=True)
        self._trace_dir = directory
        jax.profiler.start_trace(directory)
        return True

    def bench_stop_trace(self) -> bool:
        import jax

        jax.profiler.stop_trace()
        return True

    def bench_reduce_trace(self, remove: bool) -> dict:
        """Reduced here, in the process that has JAX, once the load is over."""
        from benchmarks.trace.reduce import reduce_directory

        return reduce_directory(self._trace_dir, remove=remove)

    # -- the comparison with the plain reference --------------------------

    def bench_check(self, samples: list, decode_steps: int, control: bool) -> dict:
        """For each sample (prompt, tokens the served path emitted): feed the
        emitted tokens through the engine's own paged prefill and decode
        programs, all samples side by side in the decode slots. Two
        comparisons: the logits of every position fed against the reference's
        full forward pass over the same tokens (a tolerance), and the tokens
        the greedy decode program gives at those positions against the tokens
        that were served (exact: the same program on the same inputs, whoever
        shared the batch). The engine has to be idle: its pool is used, and
        given back empty."""
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.serve.llm.kv_cache import BlockTable

        reference = self._family.reference()
        eng = self._engine
        ecfg = eng.cfg
        if eng._has_active() or eng._waiting:
            raise RuntimeError("bench_check needs an idle engine")
        if len(samples) > ecfg.max_batch:
            raise ValueError("more samples than decode slots")
        params = eng.params
        tables, engine_logits, replayed = [], [[] for _ in samples], [[] for _ in samples]
        try:
            for i, (prompt, emitted) in enumerate(samples):
                table = BlockTable(eng._alloc)
                tables.append(table)
                table.reserve(len(prompt))
                table.length = len(prompt)
                toks = np.zeros((1, eng._bucket(len(prompt))), np.int32)
                toks[0, : len(prompt)] = prompt
                bt = np.asarray([table.as_list(ecfg.max_blocks_per_seq)], np.int32)
                logits, eng._pool = eng._prefill(
                    params, jnp.asarray(toks), jnp.asarray(bt), eng._pool, jnp.int32(len(prompt)))
                engine_logits[i].append(np.asarray(logits[0]))
                replayed[i].append(int(engine_logits[i][0].argmax()))  # as the engine picks a first token
            steps = [min(decode_steps, len(e) - 1) for _, e in samples]
            for t in range(max(steps)):
                b, mb = ecfg.max_batch, ecfg.max_blocks_per_seq
                tokens, positions = np.zeros((b,), np.int32), np.zeros((b,), np.int32)
                bts, active = np.zeros((b, mb), np.int32), np.zeros((b,), bool)
                for i, (prompt, emitted) in enumerate(samples):
                    if t < steps[i]:
                        positions[i] = tables[i].length
                        tables[i].append_token()
                        tokens[i], bts[i], active[i] = emitted[t], tables[i].as_list(mb), True
                logits, eng._pool = eng._decode(
                    params, jnp.asarray(tokens), jnp.asarray(positions), jnp.asarray(bts),
                    eng._pool, jnp.asarray(active))
                # the same step again through the program the served path runs
                # (it writes the same rows): its tokens have to be the served ones
                greedy, eng._pool = eng._decode_greedy(
                    params, jnp.asarray(tokens), jnp.asarray(positions), jnp.asarray(bts),
                    eng._pool, jnp.asarray(active))
                logits, greedy = np.asarray(logits), np.asarray(greedy)
                for i in range(len(samples)):
                    if t < steps[i]:
                        engine_logits[i].append(logits[i])
                        replayed[i].append(int(greedy[i]))
        finally:
            for table in tables:
                table.release()

        pad_to = max(eng._bucket(len(p) + s + 1) for (p, _), s in zip(samples, steps))
        rel_err, gap = [], []
        ctl = {prec: {"err": [], "gap": []} for prec in CONTROLS} if control else {}
        for i, (prompt, emitted) in enumerate(samples):
            n = steps[i] + 1  # positions compared: the prompt's last, then each token fed
            seq = np.zeros((pad_to,), np.int32)
            fed = list(prompt) + list(emitted[: steps[i]])
            seq[: len(fed)] = fed
            rows = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
            ref = np.asarray(reference.logits_at(params, seq, rows, "f32"))
            got = np.stack(engine_logits[i])
            served = np.asarray(emitted[:n])
            rms = np.sqrt(np.mean(ref**2, axis=-1))
            rel_err.extend((np.linalg.norm(got - ref, axis=-1) / np.linalg.norm(ref, axis=-1)).tolist())
            gap.extend(((ref.max(-1) - ref[np.arange(n), served]) / rms).tolist())
            for prec, c in ctl.items():
                low = np.asarray(reference.logits_at(params, seq, rows, prec))
                c["err"].extend((np.linalg.norm(low - ref, axis=-1) / np.linalg.norm(ref, axis=-1)).tolist())
                c["gap"].extend(((ref.max(-1) - ref[np.arange(n), low.argmax(-1)]) / rms).tolist())
        mismatches = sum(a != b for i, (_, emitted) in enumerate(samples)
                         for a, b in zip(replayed[i], emitted))
        out = {
            "positions": len(rel_err), "served_token_mismatches": mismatches,
            "logits_rel_err_max": max(rel_err), "logits_rel_err_mean": sum(rel_err) / len(rel_err),
            "served_gap_max": max(gap),
        }
        for prec, c in ctl.items():
            out[f"control_{prec}"] = {
                "logits_rel_err_max": max(c["err"]), "logits_rel_err_mean": sum(c["err"]) / len(c["err"]),
                "logits_rel_err_min": min(c["err"]), "served_gap_max": max(c["gap"]),
            }
        return out
