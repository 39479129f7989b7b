"""Where the persistent compilation cache lands (launch/compile_cache.py).

Each case runs in a fresh subprocess: pointing the cache somewhere is a
process-global JAX setting, and the test workers must keep theirs off.
"""
import json
import os
import subprocess
import sys

from conftest import REPO_ROOT

SCRIPT = r'''
import json, sys
import jax, jax.numpy as jnp
from repro.launch.compile_cache import DEFAULT_DIR, use_compile_cache
where = use_compile_cache()
if sys.argv[1] == 'compile':
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.arange(8.0)).block_until_ready()
print(json.dumps({'where': where, 'default': DEFAULT_DIR,
                  'config': jax.config.jax_compilation_cache_dir}))
'''


def _run(env, mode):
    env = dict(env, JAX_PLATFORMS='cpu',
               PYTHONPATH=os.path.join(REPO_ROOT, 'src'))
    r = subprocess.run([sys.executable, '-c', SCRIPT, mode], env=env,
                       capture_output=True, text=True, timeout=300,
                       cwd=REPO_ROOT)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_placed_cache_dir_is_honoured_and_filled(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: nothing is overridden, and the
    compiled program is written there."""
    placed = tmp_path / 'placed'
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(placed))
    out = _run(env, 'compile')
    assert out['where'] == str(placed) == out['config']
    assert placed.is_dir() and any(placed.iterdir())


def test_default_cache_dir_is_fixed_and_git_ignored():
    """Unset: the fixed <repo>/.jax_cache, which git ignores."""
    env = {k: v for k, v in os.environ.items()
           if k != 'JAX_COMPILATION_CACHE_DIR'}
    out = _run(env, 'config')
    want = os.path.join(REPO_ROOT, '.jax_cache')
    assert out['where'] == out['config'] == out['default']
    assert os.path.realpath(out['where']) == os.path.realpath(want)
    with open(os.path.join(REPO_ROOT, '.gitignore')) as f:
        assert '.jax_cache/' in f.read().split()
