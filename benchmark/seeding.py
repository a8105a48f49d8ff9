"""One rule for turning ``--seed`` into jax keys. The driver's seeds run
a little past 2**31, more than a signed 32-bit key seed holds: the low
31 bits seed the key and the rest is folded in, so no two seeds share a
key."""
import jax


def seed_key(seed, stream):
    """The key of ``seed`` for one named purpose (``stream``: a small
    whole number, one per use, so weights and batches never share
    draws)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must not be negative, got {seed}")
    key = jax.random.key(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, seed >> 31)
    return jax.random.fold_in(key, stream)
