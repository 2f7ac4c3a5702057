"""The driver's artifact entry point entry() must compile-check anywhere."""


def test_entry_is_jittable():
    import __graft_entry__ as g  # conftest puts the repo root on sys.path

    fn, args = g.entry()
    import jax

    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)
