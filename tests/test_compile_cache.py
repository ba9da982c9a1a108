"""Where the persistent compilation cache goes (path resolution only; the
JAX config is never touched here)."""
from pathlib import Path

from repro.launch.compile_cache import ENV_VAR, compile_cache_dir

CHECKOUT = Path(__file__).resolve().parents[1]


def test_env_var_names_the_directory():
    assert compile_cache_dir({ENV_VAR: "/some/where"}) == "/some/where"


def test_unset_is_a_fixed_dir_at_the_checkout_root():
    path = compile_cache_dir({})
    assert path == str(CHECKOUT / ".jax_cache")
    assert compile_cache_dir({"TMPDIR": "/elsewhere"}) == path


def test_empty_env_var_counts_as_unset():
    assert compile_cache_dir({ENV_VAR: ""}) == str(CHECKOUT / ".jax_cache")
