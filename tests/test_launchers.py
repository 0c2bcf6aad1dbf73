"""The launcher CLIs (train/serve) run end to end."""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).parent.parent
ENV = dict(os.environ, PYTHONPATH=str(ROOT / "src"))


def _run(mod, *args, timeout=240):
    return subprocess.run([sys.executable, "-m", mod, *args],
                          capture_output=True, text=True, env=ENV, cwd=ROOT,
                          timeout=timeout)


def test_train_cli(tmp_path):
    out = _run("repro.launch.train", "--arch", "mamba2-130m", "--steps", "12",
               "--ckpt-dir", str(tmp_path), "--ckpt-every", "6")
    assert out.returncode == 0, out.stderr
    assert "done: 12 steps" in out.stdout
    assert list(tmp_path.glob("step_*")), "checkpoint not written"
    # resume from the checkpoint
    out2 = _run("repro.launch.train", "--arch", "mamba2-130m", "--steps", "14",
                "--ckpt-dir", str(tmp_path), "--resume")
    assert out2.returncode == 0, out2.stderr
    assert "resumed from step" in out2.stdout


def test_serve_cli():
    out = _run("repro.launch.serve", "--scheduler", "hiku", "--workers", "2",
               "--endpoints", "2", "--requests", "5", "--fail-at", "2")
    assert out.returncode == 0, out.stderr
    assert "failed; worker" in out.stdout  # failure + elastic join happened
    assert "summary:" in out.stdout


def test_serve_cli_compile_cache_in_env_dir(tmp_path):
    """With ``JAX_COMPILATION_CACHE_DIR`` set the launcher's compiles are
    cached there; without it the helper names the checkout's fixed
    ``.jax_cache`` (probed without compiling anything)."""
    cache = tmp_path / "cache"
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--workers", "1",
         "--endpoints", "1", "--requests", "2"],
        capture_output=True, text=True, cwd=ROOT, timeout=240,
        env=dict(ENV, JAX_COMPILATION_CACHE_DIR=str(cache)),
    )
    assert out.returncode == 0, out.stderr
    assert any(cache.iterdir()), "no cache entries in JAX_COMPILATION_CACHE_DIR"
    env = {k: v for k, v in ENV.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    probe = subprocess.run(
        [sys.executable, "-c",
         "import jax; from repro.utils.compile_cache import enable_compile_cache; "
         "print(enable_compile_cache()); print(jax.config.jax_compilation_cache_dir)"],
        capture_output=True, text=True, cwd=tmp_path, timeout=120, env=env,
    )
    assert probe.returncode == 0, probe.stderr
    assert probe.stdout.split() == [str(ROOT / ".jax_cache")] * 2


def test_chip_smoke_refuses_without_tpu():
    """Off the TPU chip_smoke exits nonzero, names the missing TPU and prints
    no result."""
    env = dict(ENV, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         capture_output=True, text=True, cwd=ROOT, timeout=120, env=env)
    assert out.returncode != 0
    assert "no TPU" in out.stderr and '"ok"' not in out.stdout


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("n_prefix", [0, 400])
def test_chip_smoke_dispatch_check_interpreted(n_prefix):
    """The smoke's dispatch check at a test width: its seeded stream is
    mixed and causally consistent (the host scheduler accepts every FINISH
    and EVICT), and the interpreted kernel equals the scan and the host
    scheduler on it, from an empty or a prefix-shaped start state."""
    cs = _chip_smoke()
    (idle0, _), ev, assign, _, _ = cs.make_stream(24, 600, seed=3, n_prefix=n_prefix)
    kinds = np.bincount(ev[:, 0], minlength=3)
    assert kinds.min() > 0, kinds
    assert (assign[ev[:, 0] == 0] >= 0).all() and (assign[ev[:, 0] != 0] == -1).all()
    assert (idle0.sum() > 0) == (n_prefix > 0)
    cs.check_dispatch(24, 600, seed=3, n_prefix=n_prefix, interpret=True)


def test_chip_smoke_reference_gaps():
    """The served phase's CPU reference: the greedy tokens of the endpoint
    itself trail its best logit by nothing, and tokens picked at random
    trail it by far more than the smoke's limit."""
    cs = _chip_smoke()
    from repro.configs import get_config
    from repro.serving import Endpoint, Instance

    ep = Endpoint("m", get_config("mamba2_130m").reduced(), seed=1)
    prompt = np.arange(1, 9, dtype=np.int32)[None]
    greedy = np.asarray(Instance(ep).generate(jnp.asarray(prompt), cs.GEN_LEN))
    assert cs.reference_gaps(ep, prompt, greedy) == [0.0] * cs.GEN_LEN
    rng = np.random.default_rng(0)
    wrong = rng.integers(0, ep.cfg.vocab, greedy.shape).astype(np.int32)
    assert max(cs.reference_gaps(ep, prompt, wrong)) > 10 * cs.MAX_GAP_STD
