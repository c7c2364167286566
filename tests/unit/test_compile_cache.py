"""The worker's compile-cache resolver (runtime/compile_cache.py): the
cache can be placed from outside, and is otherwise one fixed directory
in the checkout that every process agrees on."""

import os
import subprocess
import sys
import tempfile

from nbdistributed_tpu.runtime import compile_cache

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_env_var_leaves_the_choice_to_jax():
    assert compile_cache.resolve(
        {"JAX_COMPILATION_CACHE_DIR": "/some/where"}) is None


def test_default_is_a_fixed_dir_in_the_checkout():
    path = compile_cache.resolve({})
    assert path == compile_cache.DEFAULT_DIR
    assert os.path.dirname(path) == REPO_ROOT
    # Nothing that moves between runs: the path is part of the key.
    assert not path.startswith(tempfile.gettempdir() + os.sep)
    name = os.path.basename(path)
    assert not any(ch.isdigit() for ch in name)
    assert "NBD_RUN_DIR" not in os.environ or \
        os.environ["NBD_RUN_DIR"] not in path


def test_two_processes_resolve_the_same_path():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    code = ("from nbdistributed_tpu.runtime import compile_cache;"
            "print(compile_cache.resolve())")
    outs = [subprocess.run([sys.executable, "-c", code], env=env,
                           cwd=tempfile.gettempdir(), text=True,
                           capture_output=True, timeout=60,
                           check=True).stdout.strip()
            for _ in range(2)]
    assert outs[0] == outs[1] == compile_cache.DEFAULT_DIR
