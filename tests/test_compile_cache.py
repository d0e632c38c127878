"""Start-up contract of the package: importing it starts no JAX backend
(so an importer never holds a chip it did not ask for), and the compile
cache lands where ``JAX_COMPILATION_CACHE_DIR`` says, else in one fixed
directory of the checkout.  Each case runs in a fresh interpreter: the
test process's own backend and cache settings are already fixed."""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

_SRC = Path(__file__).resolve().parents[1] / "src"


def _run(code: str, **env) -> str:
    full = dict(os.environ)
    full.pop("JAX_COMPILATION_CACHE_DIR", None)
    full.update(
        PYTHONPATH=str(_SRC), JAX_PLATFORMS="cpu",
        # cache even this tiny compile
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0", **env,
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=full, capture_output=True,
        text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    return out.stdout.strip()


def test_import_starts_no_backend():
    code = (
        "import pkgutil, importlib, repro\n"
        "from jax._src import xla_bridge\n"
        "for m in pkgutil.walk_packages(repro.__path__, 'repro.'):\n"
        "    importlib.import_module(m.name)\n"
        "    assert not xla_bridge.backends_are_initialized(), m.name\n"
        "print('ok')\n"
    )
    assert _run(code) == "ok"


_COMPILE = (
    "import jax, jax.numpy as jnp\n"
    "from repro.compile_cache import enable_compile_cache\n"
    "print(enable_compile_cache())\n"
    "jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()\n"
)


def test_cache_follows_the_environment_variable(tmp_path):
    where = _run(_COMPILE, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert where == str(tmp_path)
    assert list(tmp_path.glob("jit__lambda-*"))


def test_cache_defaults_to_the_checkout():
    from repro.compile_cache import CHECKOUT_CACHE_DIR

    assert CHECKOUT_CACHE_DIR.parent == _SRC.parent
    where = _run(_COMPILE)
    assert where == str(CHECKOUT_CACHE_DIR)
    # written now, or by an earlier run (the key is deterministic)
    assert list(CHECKOUT_CACHE_DIR.glob("jit__lambda-*"))


def test_closure_tables_do_not_depend_on_the_hash_seed():
    # the CNF's nonterminal indices are baked into every closure
    # executable, so they fix its persistent-cache key
    code = (
        "from repro.core.grammar import query1_grammar, query2_grammar\n"
        "from repro.core.matrices import ProductionTables\n"
        "for g in (query1_grammar(), query2_grammar()):\n"
        "    cnf = g.to_cnf()\n"
        "    print(cnf.nonterms, ProductionTables.from_grammar(cnf))\n"
    )
    outs = {_run(code, PYTHONHASHSEED=str(seed)) for seed in range(6)}
    assert len(outs) == 1, outs
