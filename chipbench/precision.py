"""The precision one step below float32 at ``highest``, made explicit so
that it computes the same on any backend."""


def _split_bf16(a):
    """Three-pass operands: ``a ~ hi + lo`` with both parts bfloat16."""
    import jax.numpy as jnp
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def dot_high(a, b):
    """``a @ b`` in float32 as three bfloat16 passes (hi*hi + hi*lo +
    lo*hi, float32 accumulation): what ``Precision.HIGH`` computes."""
    import jax.numpy as jnp
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)
    mm = lambda x, y: jnp.matmul(x, y, preferred_element_type=jnp.float32)  # noqa: E731
    return mm(ah, bh) + mm(ah, bl) + mm(al, bh)
