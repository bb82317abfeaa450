"""The one compile-cache rule every entry point follows
(``repro.launch.compile_cache``). Each case runs in a fresh interpreter:
the helper exports an environment variable and updates JAX's config,
which must not leak into the test process."""
import os
import subprocess
import sys

from conftest import REPO, SRC


def _run(code: str, **env_over) -> str:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=SRC, JAX_PLATFORMS="cpu", **env_over)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


REPO_CACHE = os.path.join(REPO, ".jax_cache")

# cache every compile, however small, then compile one program
_COMPILE = """
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
jax.jit(lambda x: jnp.sin(x) * {k}.0)(jnp.arange(8.0)).block_until_ready()
"""


def _entries(path: str) -> set:
    return set(os.listdir(path)) if os.path.isdir(path) else set()


def test_unset_defaults_to_repo_cache():
    """Unset: ``<repo>/.jax_cache``, exported for children, applied to an
    already-imported JAX, and where a compiled program is written."""
    out = _run("""
import os, jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
path = enable_compile_cache()
print(path)
print(os.environ["JAX_COMPILATION_CACHE_DIR"])
print(jax.config.jax_compilation_cache_dir)
""" + _COMPILE.format(k=5))
    assert out.split() == [REPO_CACHE] * 3
    assert _entries(REPO_CACHE), "no cache entry written to the repo cache"


def test_env_var_wins_and_cache_lands_only_there(tmp_path):
    """Set: the helper changes nothing, compiled programs are written to
    that directory, and the repo default is neither used nor written."""
    cache = tmp_path / "cc"
    before = _entries(REPO_CACHE)
    out = _run(f"""
import jax, jax.numpy as jnp
from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache
assert enable_compile_cache() == {str(cache)!r}
assert jax.config.jax_compilation_cache_dir == {str(cache)!r}
print(REPO_CACHE_DIR)
""" + _COMPILE.format(k=3), JAX_COMPILATION_CACHE_DIR=str(cache))
    assert os.listdir(cache), "no cache entry written"
    assert out.strip() == REPO_CACHE
    assert _entries(REPO_CACHE) == before, "the repo cache was written"


# one program whose named scope is {scope}; prints the scopes its
# executable (compiled or loaded from the cache) carries and the hits
_SCOPED = """
import re, jax, jax.numpy as jnp, numpy as np
from repro.launch.compile_cache import enable_compile_cache
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
hits = []
jax.monitoring.register_event_listener(
    lambda e, **k: hits.append(e) if e.endswith("cache_hits") else None)
def f(x):
    with jax.named_scope({scope!r}):
        return jnp.sin(x) * 7.0
text = jax.jit(f).lower(np.arange(8.0, dtype=np.float32)).compile().as_text()
print(sorted(set(re.findall(r"engine[.][a-z]+", text))), len(hits))
"""


def test_cached_executables_keep_their_scopes(tmp_path):
    """The same program run again loads its executable from the cache; the
    program with only its named scope changed compiles anew, so a profile
    never shows another version's scopes."""
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc")}
    first = _run(_SCOPED.format(scope="engine.old"), **env).split("]")
    again = _run(_SCOPED.format(scope="engine.old"), **env).split("]")
    renamed = _run(_SCOPED.format(scope="engine.new"), **env).split("]")
    assert first == ["['engine.old'", " 0\n"]
    assert again == ["['engine.old'", " 1\n"]
    assert renamed == ["['engine.new'", " 0\n"]
