"""Process-level JAX setup (flink_tpu/core/jaxenv.py): where the compile
cache lives, and the checks that keep a chip run off the CPU. Settings
that JAX reads at start-up are checked in a fresh interpreter."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _fresh(code: str, **env) -> dict:
    """Run ``code`` in a new interpreter at the repo root; it prints one
    JSON object as its last line."""
    full = {k: v for k, v in os.environ.items()
            if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")}
    full.update(env)
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=full,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


_CACHE = ("import json, jax; from flink_tpu.core.jaxenv import "
          "use_compile_cache; p = use_compile_cache(); print(json.dumps("
          "[p, jax.config.jax_compilation_cache_dir]))")


def test_compile_cache_follows_the_environment_variable(tmp_path):
    want = str(tmp_path / "cache")
    assert _fresh(_CACHE, JAX_PLATFORMS="cpu",
                  JAX_COMPILATION_CACHE_DIR=want) == [want, want]


def test_compile_cache_defaults_to_an_ignored_path_in_the_checkout():
    path, configured = _fresh(_CACHE, JAX_PLATFORMS="cpu")
    assert path == configured == str(ROOT / ".jax_cache")
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split("\n")


def test_require_accelerator_refuses_the_cpu():
    from flink_tpu.core.jaxenv import require_accelerator

    with pytest.raises(RuntimeError, match="no accelerator"):
        require_accelerator()


@pytest.mark.parametrize("named,expect", [(None, "tpu"), ("cpu", "cpu")])
def test_workers_pin_the_tpu_unless_a_platform_is_named(named, expect):
    """A cluster or DCN worker with no JAX_PLATFORMS must fail where the
    chip fails, not fall back to the CPU; the controller that starts it
    never initializes a JAX backend."""
    code = (
        "import json, jax; "
        "import flink_tpu.runtime.process_cluster; "
        "from jax._src import xla_bridge; "
        "idle = not xla_bridge._backends; "
        "from flink_tpu.core.jaxenv import refuse_cpu_fallback; "
        "refuse_cpu_fallback(); "
        "print(json.dumps([idle, jax.config.jax_platforms]))"
    )
    env = {"JAX_PLATFORMS": named} if named else {}
    assert _fresh(code, **env) == [True, expect]
