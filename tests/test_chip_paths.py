"""The refusals that keep a run from passing off the chip in silence: the
flash kernel raises instead of giving way, ``bench.py`` knows no device it
was not told about, a worker's JAX platform follows the resources it holds,
and ``chip_smoke.py`` cannot say ``"ok": true`` on the CPU."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, env_extra, timeout=600):
    """Runs in a session of its own; ``.left_behind`` lists what is still in
    that session, zombies included, the moment the command has ended."""
    env = dict(os.environ)
    env.update(env_extra)
    p = subprocess.Popen(
        [sys.executable, *args], cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, start_new_session=True,
    )
    try:
        out, err = p.communicate(timeout=timeout)
    finally:
        left = _in_session(p.pid)
        if left:
            os.killpg(p.pid, 9)
    r = subprocess.CompletedProcess(p.args, p.returncode, out, err)
    r.left_behind = left
    return r


def _in_session(sid):
    found = []
    for name in os.listdir("/proc"):
        if name.isdigit():
            try:
                with open(f"/proc/{name}/stat") as f:
                    fields = f.read().rsplit(")", 1)[1].split()
                with open(f"/proc/{name}/cmdline") as f:
                    cmd = f.read().replace("\0", " ")[:120]
            except OSError:
                continue
            if int(fields[3]) == sid:  # state ppid pgrp session
                found.append((int(name), fields[0], cmd))
    return found


def test_flash_raises_instead_of_falling_back(monkeypatch):
    """The TPU kernel cannot lower on the CPU backend: ``_flash`` says so,
    and ``attention`` — once shape and platform chose the kernel — does not
    quietly run the einsum path instead."""
    import jax.numpy as jnp

    import ray_tpu.ops  # noqa: F401

    A = sys.modules["ray_tpu.ops.attention"]  # ray_tpu.ops.attention is the function

    q = jnp.ones((1, 128, 2, 128), jnp.float32)
    with pytest.raises(Exception) as direct:
        A._flash(q, q, q, causal=True)
    monkeypatch.setattr(A, "_can_use_flash", lambda q, k: True)
    with pytest.raises(type(direct.value)):
        A.attention(q, q, q, causal=True)
    # and the choice itself is shape + platform: never the kernel off a TPU
    monkeypatch.undo()
    assert not A._can_use_flash(q, q)


def test_bench_peak_lookup_refuses_unknown_device(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))  # bench.py defaults it
    monkeypatch.syspath_prepend(REPO)
    import bench

    assert bench.peak_bf16_flops("TPU v5 lite") == 197e12
    with pytest.raises(ValueError, match="no bf16 peak recorded"):
        bench.peak_bf16_flops("TPU v9 mystery")
    with pytest.raises(ValueError):
        bench.peak_bf16_flops("cpu")


def test_subset_of_a_host_gets_bounds_and_the_whole_host_nothing():
    from ray_tpu._private.accelerators import tpu

    assert tpu.visible_chip_env([0, 1, 2, 3], 4) == {}
    assert tpu.visible_chip_env([0], 1) == {}
    assert tpu.visible_chip_env([2], 4) == {
        "TPU_VISIBLE_CHIPS": "2",
        "TPU_CHIPS_PER_HOST_BOUNDS": "1,1,1",
        "TPU_HOST_BOUNDS": "1,1,1",
    }
    assert tpu.visible_chip_env([2, 3], 4)["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,2,1"


_WORKER_PLATFORM_SCRIPT = textwrap.dedent(
    """
    import json, os, sys
    import ray_tpu

    def look(check):
        import jax
        out = {"env": os.environ.get("JAX_PLATFORMS")}
        try:
            if check:
                from ray_tpu.train.jax_utils import ensure_platform
                ensure_platform()
            out["backend"] = jax.default_backend()
        except RuntimeError as e:
            out["error"] = str(e)
        with open("/proc/self/maps") as f:
            out["libtpu"] = "libtpu" in f.read()
        return out

    if __name__ == "__main__":
        ray_tpu.init(num_cpus=2, resources={"TPU": 1})
        plain = ray_tpu.remote(look)
        holder = ray_tpu.remote(resources={"TPU": 1})(look)
        out = {
            "plain": ray_tpu.get(plain.remote(True), timeout=120),
            "holder": ray_tpu.get(holder.remote(sys.argv[1] == "check"), timeout=120),
            "driver_touched_jax": "jax" in sys.modules,
        }
        ray_tpu.shutdown()
        print("RESULT " + json.dumps(out))
    """
)


@pytest.mark.parametrize(
    "inherited,holder_check,holder_says",
    [
        # the machine with the chip: a worker that holds the (here: missing)
        # chip must get the TPU or fail — JAX's own strict start-up
        ("tpu,cpu", "nocheck", "Unable to initialize backend 'tpu'"),
        # a forced CPU under a TPU resource: ensure_platform refuses it
        ("cpu", "check", "refusing to run off the chip"),
    ],
)
def test_worker_platform_follows_its_resources(tmp_path, inherited, holder_check, holder_says):
    """A worker without a ``TPU`` resource is held to the CPU backend whatever
    the driver's ``JAX_PLATFORMS`` says, and never maps libtpu; one that holds
    a chip fails rather than compute anywhere else. The driver stays off jax."""
    script = tmp_path / "look.py"
    script.write_text(_WORKER_PLATFORM_SCRIPT)
    r = _run([str(script), holder_check], {"JAX_PLATFORMS": inherited, "PYTHONPATH": REPO})
    lines = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")]
    assert r.returncode == 0 and lines, r.stdout[-2000:] + r.stderr[-2000:]
    out = json.loads(lines[-1][len("RESULT "):])
    assert out["plain"] == {"env": "cpu", "backend": "cpu", "libtpu": False}
    assert holder_says in out["holder"].get("error", ""), out["holder"]
    assert "backend" not in out["holder"]
    assert out["driver_touched_jax"] is False


def test_chip_smoke_tiny_cannot_pass_off_the_chip(tmp_path):
    """The rehearsal runs both phases end to end on the CPU, names the device
    it ran on, exits non-zero, and never prints the contract's last line."""
    r = _run(
        ["chip_smoke.py", "--tiny"],
        {"JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")},
    )
    assert r.returncode == 3, r.stdout[-3000:] + r.stderr[-3000:]
    assert '"ok": true' not in r.stdout
    assert "rehearsal at TINY size passed on cpu" in r.stdout.splitlines()[-1]
    assert "greedy tokens of prompt 0 sent alone == its tokens among the other five" in r.stdout
    assert "losses finite and falling" in r.stdout
    assert "every process this run started has ended" in r.stdout
    assert not r.left_behind, r.left_behind


def test_chip_smoke_refuses_a_host_without_chips(tmp_path):
    """Without ``--tiny`` there is no rehearsal: no chip, no run, no result."""
    r = _run(
        ["chip_smoke.py"],
        {"JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")},
    )
    assert r.returncode not in (0, 3)
    assert '"ok"' not in r.stdout
    assert "needs 1 TPU chip(s)" in r.stderr
    assert not r.left_behind, r.left_behind  # the failed run too stops what it started
