"""Persistent XLA compilation cache (utils/platform.enable_compilation_cache,
wired at package import). Three-way precedence: a set
JAX_COMPILATION_CACHE_DIR is respected and never overwritten; else
TMOG_COMPILE_CACHE_DIR (0 disables); else one fixed directory inside the
checkout, the same from any working directory and in any process."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRINT_DIR = ("import transmogrifai_tpu, jax\n"
              "print(repr(jax.config.jax_compilation_cache_dir))\n")


def _run(code, cwd=None, **env_overrides):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",
                        "TMOG_COMPILE_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env.update(env_overrides)
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=cwd,
                       capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stderr[-500:]
    return r.stdout.strip().splitlines()[-1]


def test_jax_env_var_is_respected_and_never_overwritten(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the package must not call
    config.update on the directory at all — not at import, not in
    force_cpu's second call, and not in favour of TMOG_COMPILE_CACHE_DIR."""
    placed = str(tmp_path / "placed")
    code = ("import jax\n"
            "seen = []\n"
            "orig = jax.config.update\n"
            "def spy(name, val):\n"
            "    seen.append(name)\n"
            "    return orig(name, val)\n"
            "jax.config.update = spy\n"
            "import transmogrifai_tpu\n"
            "from transmogrifai_tpu.utils.platform import (\n"
            "    compile_cache_dir, force_cpu)\n"
            "force_cpu(2)\n"
            "assert 'jax_compilation_cache_dir' not in seen, seen\n"
            "assert compile_cache_dir() == jax.config."
            "jax_compilation_cache_dir\n"
            "print(repr(jax.config.jax_compilation_cache_dir))\n")
    out = _run(code, JAX_COMPILATION_CACHE_DIR=placed,
               TMOG_COMPILE_CACHE_DIR=str(tmp_path / "other"))
    assert out == repr(placed)


def test_default_is_one_fixed_dir_inside_the_checkout(tmp_path):
    want = repr(os.path.join(REPO, ".jax_cache", "cpu"))
    assert _run(_PRINT_DIR, cwd=REPO) == want
    assert _run(_PRINT_DIR, cwd=str(tmp_path)) == want   # any cwd, any pid
    # a process that did not pin the CPU gets the other leaf
    assert _run(_PRINT_DIR, JAX_PLATFORMS="") == repr(
        os.path.join(REPO, ".jax_cache", "tpu"))
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_tmog_dir_populates_and_hits(tmp_path):
    """A fresh TMOG_COMPILE_CACHE_DIR gains entries on first compile; a
    second process with the same program loads from it (a HIT writes
    nothing new: same program, same fingerprint — a miss would recompile
    and add fresh entries)."""
    code = (
        "import numpy as np, jax, jax.numpy as jnp\n"
        "import transmogrifai_tpu\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return jnp.tanh(x @ x.T).sum()\n"
        "print(float(f(np.ones((300, 300), np.float32))))\n"
    )
    first = _run(code, TMOG_COMPILE_CACHE_DIR=str(tmp_path))
    entries = set(os.listdir(tmp_path))
    assert entries, "no cache entries written"
    assert _run(code, TMOG_COMPILE_CACHE_DIR=str(tmp_path)) == first
    assert set(os.listdir(tmp_path)) == entries


def test_tmog_zero_disables():
    assert _run(_PRINT_DIR, TMOG_COMPILE_CACHE_DIR="0") in ("None", "''")
