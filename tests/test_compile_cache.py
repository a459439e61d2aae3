"""The compile-cache rule (PR 23): one function decides where the
persistent XLA cache lives, a directory given from outside wins, and
nothing else in the tree sets one."""
import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = (
    "import json, jax\n"
    "from paddle_tpu.utils.compile_cache import enable_compile_cache\n"
    "before = jax.config.jax_compilation_cache_dir\n"
    "a, b = enable_compile_cache(), enable_compile_cache()\n"
    "print(json.dumps([before, a, b,"
    " jax.config.jax_compilation_cache_dir]))\n")


def _probe(env_dir):
    """Run the helper twice in a child (this process must not turn a
    cache on: six test workers share the checkout)."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=HERE)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    r = subprocess.run([sys.executable, "-c", _PROBE], env=env, cwd=HERE,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("placed", [True, False])
def test_cache_dir_is_placed_from_outside_or_fixed(placed, tmp_path):
    env_dir = str(tmp_path / "cache") if placed else None
    before, a, b, after = _probe(env_dir)
    assert a == b
    if placed:
        # JAX read the variable itself; the helper left its setting alone
        assert before == after == a == env_dir
    else:
        assert before is None
        assert a == after == os.path.join(HERE, ".jax_cache")


def _tracked_py_files():
    """The *.py files git would commit: the tree minus what .gitignore
    lists (the driver's checkout need not be a git repository)."""
    with open(os.path.join(HERE, ".gitignore")) as f:
        ignored = {line.strip().rstrip("/") for line in f if line.strip()}
    for root, dirs, names in os.walk(HERE):
        dirs[:] = [d for d in dirs if d not in ignored and d != ".git"]
        for name in names:
            if name.endswith(".py"):
                yield os.path.relpath(os.path.join(root, name), HERE)


def test_only_the_helper_sets_a_cache_dir():
    setters = sorted(
        f for f in _tracked_py_files()
        if re.search(r"jax_compilation_cache_dir|set_cache_dir|"
                     r"initialize_cache",
                     open(os.path.join(HERE, f)).read())
        and f != os.path.join("tests", "test_compile_cache.py"))
    assert setters == [os.path.join("paddle_tpu", "utils",
                                    "compile_cache.py")], setters
