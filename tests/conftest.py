import jax

# fp64 for the ranking oracles. Do NOT set XLA_FLAGS here — tests and
# benches must see the real single-device CPU; the multi-device tests spawn
# their own processes.
jax.config.update("jax_enable_x64", True)

# Property-test modules import hypothesis at module level; on bare
# environments install the deterministic fallback so tier-1 still collects
# (and exercises) all modules. The real package wins when present.
try:
    import hypothesis  # noqa: F401
except ImportError:
    from _hypothesis_fallback import install as _install_hypothesis_fallback

    _install_hypothesis_fallback()
