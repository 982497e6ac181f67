"""harness/scopes.py against the program, by hand like the rest of this
directory: the table of the cell's compiled step carries the program's layer
scopes even when the compile cache was filled by a build of the program
that has none. JAX's cache key leaves HLO metadata out, so such a cache
serves that build's text under this build's key (the chip tool's machine
keeps one cache across PRs: PR 26 met PR 24's executable there).
"""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CHILD = r'''
import contextlib, sys
sys.path.insert(0, ".")
import jax
if sys.argv[1] == "no-scopes":      # a build of the program without them
    @contextlib.contextmanager
    def no_scope(name):
        yield
    jax.named_scope = no_scope
from deeplearning4j_tpu.common.compile_cache import enable_compile_cache
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
import jax.numpy as jnp
from benchmarks.drivers.train import _build
from benchmarks.harness import loader, scopes
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.optimize.profiler import scope_of
cell = loader.make_cell("t", "tiny-resnet", "train-staged", 1)
if sys.argv[1] == "no-scopes":
    net, fit, _ = _build(cell["config"], 1)
    fit(DataSet(jnp.zeros((32, 48, 48, 3), jnp.bfloat16),
                jnp.zeros((32, 10), jnp.float32)))
else:
    from deeplearning4j_tpu.optimize.profiler import op_scopes
    table = op_scopes(scopes.compiled_text(cell))
    kinds = {s[0] for s in map(scope_of, table.values()) if s}
    print("KINDS", " ".join(sorted(kinds)))
'''


def child(mode, cache):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    r = subprocess.run([sys.executable, "-c", CHILD, mode], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    return r


def test_scopes_survive_a_cache_filled_by_a_build_without_them(tmp_path):
    child("no-scopes", tmp_path)
    for _ in range(2):      # the second finds the metadata-keyed entry
        r = child("scopes", tmp_path)
        assert "carries no scopes; compiling it again" in r.stderr
        kinds = r.stdout.split("KINDS", 1)[1].split()
        assert {"batchnorm", "convolution", "update"} <= set(kinds)


def test_a_fresh_cache_needs_no_second_compile(tmp_path):
    r = child("scopes", tmp_path)
    assert "compiling it again" not in r.stderr
    assert "batchnorm" in r.stdout
