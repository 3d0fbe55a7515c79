"""The chip smoke's phases on the CPU at reduced size, the script's refusal
to run without a TPU, per-device replica placement, and the compile-cache
helper.  (The full-width run itself needs the chip: ``chip_smoke.py``.)"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.launch import compile_cache
from repro.launch.smoke import SmokeFailure, serve_smoke, session_stream
from repro.runtime.serve_loop import DiffusionServer

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
SRC = os.path.join(ROOT, "src")
CFG = get_arch("internlm2-1.8b").reduced()
SMALL = dict(prompt_len=24, cache_cap=128, new_tokens=4)


def _cpu_env(**extra):
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.update(extra)
    return env


def test_serve_smoke_phase_passes_on_cpu():
    lines = []
    out = serve_smoke(CFG, seed=0, log=lines.append, **SMALL)
    assert out["served"] == out["submitted"] == 16 and out["lost"] == 0
    assert out["prefix_hits"] > 0 and out["swap_ins"] > 0
    assert out["tokens_checked"] == 16 * SMALL["new_tokens"]
    assert out["greedy_exact"] > out["tokens_checked"] // 2
    assert any(l.startswith("reference:") and l.endswith("passed")
               for l in lines)
    assert any(l.startswith("smoke timings") for l in lines)
    assert any(l.startswith("serve:") and "staged_demotions=" in l
               for l in lines)


def test_reference_check_catches_a_wrong_swap_in(monkeypatch):
    """Mutation: a swap-in that hands decode the wrong KV must fail the
    reference check, not pass silently."""
    from repro.diffusion.payload import RealPayload
    real_value = RealPayload.value

    def zeroed(self, obj):
        v = real_value(self, obj)
        return None if v is None else jax.tree_util.tree_map(
            lambda x: x * 0, v)

    monkeypatch.setattr(RealPayload, "value", zeroed)
    with pytest.raises(SmokeFailure):
        serve_smoke(CFG, seed=0, log=lambda _: None, **SMALL)


def test_session_stream_revisits_every_session():
    stream = session_stream(6, 16, seed=0)
    assert len(stream) == 16
    assert stream[:12] == [f"s{i}" for i in range(6)] * 2
    assert stream == session_stream(6, 16, seed=0)


def test_one_device_placement_shares_one_param_copy():
    """With one device every replica lands there and shares the server's
    own parameters — no copy per replica."""
    srv = DiffusionServer(CFG, max_replicas=3, min_replicas=3, cache_cap=64)
    (dev,) = jax.local_devices()
    assert len(srv.replicas) == 3
    for rep in srv.replicas.values():
        assert rep.device == dev and rep.params is srv.params
    srv.scale_to(1)
    assert len(srv._placement) == 1


PLACEMENT_SCRIPT = textwrap.dedent("""
    import json
    from repro.configs import get_arch
    from repro.launch.smoke import placement_smoke
    out = placement_smoke(get_arch("internlm2-1.8b").reduced(), seed=0,
                          prompt_len=24, cache_cap=128, new_tokens=4,
                          log=lambda _: None)
    print(json.dumps(out))
""")


def test_placement_smoke_on_four_virtual_devices():
    """--chips 4 rehearsal: one replica per device, results identical to
    all four replicas on device 0."""
    env = _cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", PLACEMENT_SCRIPT],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["devices"] == [0, 1, 2, 3] and res["requests"] == 16
    assert res["hits"] > 0


@pytest.mark.parametrize("alone", [False, True])
def test_chip_smoke_refuses_without_tpu(tmp_path, alone):
    """No TPU (or no repo next to the script): nonzero exit, no verdict."""
    script = os.path.join(ROOT, "chip_smoke.py")
    if alone:
        script = str(tmp_path / "chip_smoke.py")
        with open(os.path.join(ROOT, "chip_smoke.py")) as src, \
                open(script, "w") as dst:
            dst.write(src.read())
    env = _cpu_env()
    env.pop("PYTHONPATH")
    out = subprocess.run([sys.executable, script], capture_output=True,
                         text=True, env=env, cwd=str(tmp_path), timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_compile_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    assert os.path.realpath(path) == os.path.join(os.path.realpath(ROOT),
                                                  ".jax_cache")


CACHE_SCRIPT = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import use_compile_cache
    print(use_compile_cache(), jax.config.jax_compilation_cache_dir)
    jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
""")


def test_compile_cache_env_dir_wins(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the helper sets nothing and entries
    land only there."""
    cache = tmp_path / "cache"
    env = _cpu_env(JAX_COMPILATION_CACHE_DIR=str(cache),
                   JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    out = subprocess.run([sys.executable, "-c", CACHE_SCRIPT],
                         capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [str(cache), str(cache)]
    assert any(cache.iterdir())


def test_compile_cache_entries_land_in_the_checkout(tmp_path):
    """JAX_COMPILATION_CACHE_DIR unset: entries land in <checkout>/.jax_cache
    of the checkout the helper file sits in, and nowhere else there."""
    checkout = tmp_path / "checkout"
    launch = checkout / "src" / "repro" / "launch"
    launch.mkdir(parents=True)
    with open(compile_cache.__file__) as src:
        (launch / "compile_cache.py").write_text(src.read())
    script = CACHE_SCRIPT.replace("from repro.launch.compile_cache",
                                  "from compile_cache")
    env = _cpu_env(PYTHONPATH=str(launch),
                   JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop(compile_cache.ENV, None)
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, cwd=str(tmp_path), timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    cache = checkout / ".jax_cache"
    assert out.stdout.split() == [str(cache), str(cache)]
    assert any(cache.iterdir())
    assert sorted(p.name for p in checkout.iterdir()) == [".jax_cache", "src"]


def test_bench_harness_exits_nonzero_on_error_row(monkeypatch, capsys):
    sys.path.insert(0, ROOT)
    from benchmarks import run

    def boom(*_a, **_k):
        raise ImportError("boom")

    monkeypatch.setattr(compile_cache, "use_compile_cache", lambda: "")
    monkeypatch.setattr(run.importlib, "import_module", boom)
    monkeypatch.setattr(sys, "argv", ["run", "--smoke", "--only", "scheduler"])
    with pytest.raises(SystemExit) as exc:
        run.main()
    assert exc.value.code not in (0, None)
    assert "scheduler/ERROR,0,ImportError:boom" in capsys.readouterr().out


def test_request_keeps_generated_tokens_on_device():
    """Generated tokens stay device arrays until read after the request."""
    srv = DiffusionServer(CFG, max_replicas=1, min_replicas=1, cache_cap=64)
    req = srv.submit("s0", np.arange(8), max_new_tokens=3)
    srv.step()
    assert len(req.generated) == 3
    assert all(isinstance(t, jax.Array) for t in req.generated)
    assert req.last_logits.shape[0] == 1
    assert int(req.generated[-1][0]) == int(np.argmax(req.last_logits[0]))
