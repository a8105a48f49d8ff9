"""``parallel/moe.py``'s expert-parallel layer (``RoutedExpertsFFN``,
``routed_experts``) at small sizes on the CPU, against the plain
reference's expert layer (``benchmark/families/laguna.py``: a masked
loop over the experts, no sort, no grouping): the shares of a
deployment add up to the uncut layer, and no row is lost whatever the
routing. ``MoEFFN``'s selection on a tie is here too.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from mxnet_tpu.ndarray.ndarray import _wrap  # noqa: E402
from mxnet_tpu.parallel import moe  # noqa: E402

from benchmark import correctness  # noqa: E402
from benchmark.families import laguna  # noqa: E402


# ---------------------------------------------------------------------------
# the shares add up
# ---------------------------------------------------------------------------

def _expert_layer_inputs(seed=3, n=48, c=16, f=8, routed=8):
    ks = jax.random.split(jax.random.key(seed), 6)
    return {
        "x": jax.random.normal(ks[0], (n, c)),
        "router_weight": jax.random.normal(ks[1], (routed, c)),
        "w_gate": 0.3 * jax.random.normal(ks[2], (routed, c, f)),
        "w_up": 0.3 * jax.random.normal(ks[3], (routed, c, f)),
        "w_down": 0.3 * jax.random.normal(ks[4], (routed, f, c))}


@pytest.mark.parametrize("shares", [2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """What every chip of the deployment computes for its own experts,
    summed over the chips, is the uncut reference layer's routed part;
    the shared expert, which every chip computes alike, counts once."""
    p = _expert_layer_inputs()
    sizes = {"num_experts_per_tok": 3, "moe_routed_scaling_factor": 2.5}
    whole, _ = laguna._experts(sizes, p["x"], p, correctness.Rounding,
                               held=(0, 8))
    per = 8 // shares
    total_program = total_reference = 0.0
    for s in range(shares):
        lo = s * per
        held = {k: p[k][lo:lo + per] for k in ("w_gate", "w_up", "w_down")}
        total_program = total_program + moe.routed_experts(
            p["x"], p["router_weight"], held["w_gate"], held["w_up"],
            held["w_down"], k=3, held_start=lo, num_held=per, scale=2.5)
        part, _ = laguna._experts(
            sizes, p["x"], {**held, "router_weight": p["router_weight"]},
            correctness.Rounding, held=(lo, per))
        total_reference = total_reference + part
    assert onp.allclose(total_reference, whole, atol=1e-5)
    assert onp.allclose(total_program, whole, atol=1e-5)
    # and a share alone is not the whole
    assert not onp.allclose(part, whole, atol=1e-3)


def test_shared_expert_counts_once_in_the_block():
    """Two RoutedExpertsFFN shares with the same shared expert: the sum
    of their outputs less one shared expert is the uncut layer."""
    p = _expert_layer_inputs(c=16, f=8)
    x = _wrap(p["x"].reshape(2, 24, 16))
    outs, shared = [], None
    for lo in (0, 4):
        blk = moe.RoutedExpertsFFN(16, 8, 8, 3, range(lo, lo + 4), 2.5,
                                   shared_hidden=8)
        blk.initialize()
        for name in ("w_gate", "w_up", "w_down"):
            getattr(blk, name).set_data(_wrap(p[name][lo:lo + 4]))
        blk.router_weight.set_data(_wrap(p["router_weight"]))
        for leaf in ("gate_proj", "up_proj", "down_proj"):
            w = getattr(blk.shared, leaf).weight
            w.set_data(_wrap(jnp.full(w.shape, 0.05)))
        outs.append(blk(x)._data)
        shared = blk.shared(x)._data
    sizes = {"num_experts_per_tok": 3, "moe_routed_scaling_factor": 2.5}
    whole, _ = laguna._experts(sizes, p["x"], p, correctness.Rounding,
                               held=(0, 8))
    got = (outs[0] + outs[1] - shared).reshape(48, 16)
    assert onp.allclose(got, whole + shared.reshape(48, 16), atol=1e-5)


# ---------------------------------------------------------------------------
# dispatch: no row is lost
# ---------------------------------------------------------------------------

def test_every_token_routed_to_one_held_expert_loses_no_row():
    """The router sends every token to expert 2 first (and to two more):
    expert 2 gets as many rows as there are tokens, the worst case of
    one group, and the output is still the reference's."""
    from mxnet_tpu.telemetry import metrics
    p = _expert_layer_inputs(n=64)
    x = jnp.abs(p["x"]) + 0.1
    router = p["router_weight"].at[2].set(5.0)      # a huge logit
    blk = moe.RoutedExpertsFFN(16, 8, 8, 3, range(0, 4), 2.5,
                               label="test.skew")
    blk.initialize()
    for name in ("w_gate", "w_up", "w_down"):
        getattr(blk, name).set_data(_wrap(p[name][:4]))
    blk.router_weight.set_data(_wrap(router))
    got = blk(_wrap(x))._data
    sizes = {"num_experts_per_tok": 3, "moe_routed_scaling_factor": 2.5}
    want, ids = laguna._experts(
        sizes, x, {**{k: p[k][:4] for k in ("w_gate", "w_up", "w_down")},
                   "router_weight": router}, correctness.Rounding,
        held=(0, 4))
    assert bool(jnp.all(jnp.any(ids == 2, axis=-1)))
    assert onp.allclose(got, want, atol=1e-5)
    counts, _ = moe.routing_counts(x, router, k=3, held_start=0,
                                   num_held=4)
    assert int(counts[2]) == 64
    held_rows = int(jnp.sum((ids >= 0) & (ids < 4)))
    assert metrics.gauge("moe_rows_routed.test.skew").value() == held_rows
    assert metrics.gauge("moe_rows_dropped.test.skew").value() == 0
    assert metrics.gauge("moe_load_max_over_mean.test.skew").value() \
        == pytest.approx(64 / (held_rows / 4))


def test_all_rows_held_fills_the_whole_buffer():
    """Every choice of every token held here (the layer holds all the
    router scores): tokens x k rows, the buffer's size, none dropped."""
    p = _expert_layer_inputs(n=40)
    got = moe.routed_experts(p["x"], p["router_weight"], p["w_gate"],
                             p["w_up"], p["w_down"], k=3, held_start=0,
                             num_held=8, scale=1.0)
    sizes = {"num_experts_per_tok": 3, "moe_routed_scaling_factor": 1.0}
    want, _ = laguna._experts(sizes, p["x"], p, correctness.Rounding,
                              held=(0, 8))
    counts, _ = moe.routing_counts(p["x"], p["router_weight"], k=3,
                                   held_start=0, num_held=8)
    assert int(counts.sum()) == 40 * 3
    assert onp.allclose(got, want, atol=1e-5)


def test_moeffn_takes_exactly_k_on_a_tie():
    """Two experts tied at the k-th place: the threshold mask took both,
    ``top_k`` takes the lower index alone."""
    x = jnp.ones((3, 4))
    gate_w = jnp.zeros((4, 4)).at[0].set(1.0)   # experts 1, 2, 3 tie
    w1 = jnp.stack([jnp.eye(4) * (e + 1) for e in range(4)])
    w2 = jnp.stack([jnp.eye(4)] * 4)
    zeros = jnp.zeros((4, 4))
    from mxnet_tpu import nd
    got = nd._moe_ffn(_wrap(x), _wrap(gate_w), _wrap(w1), _wrap(zeros),
                      _wrap(w2), _wrap(zeros),
                      num_experts_per_tok=2)._data
    probs = jax.nn.softmax(x @ gate_w.T, axis=-1)[0]
    gates = jnp.array([probs[0], probs[1], 0.0, 0.0])
    gates = gates / gates.sum()
    gelu = jax.nn.gelu(jnp.array([1.0, 2.0]), approximate=False)
    want = gates[0] * gelu[0] + gates[1] * gelu[1]
    assert onp.allclose(got, want, atol=1e-6)




# ---------------------------------------------------------------------------
# past the buffer: further passes, every row still computed
# ---------------------------------------------------------------------------

TOKENS, WIDTH, CHOICES, ROUTED, HELD = 48, 32, 4, 32, 4
BUFFER = moe.buffer_rows(TOKENS * CHOICES, HELD, ROUTED)


def _routing_this_many_rows_here(rows, seed=5):
    """Inputs whose router sends exactly ``rows`` of the TOKENS x
    CHOICES (token, choice) rows to experts 0..HELD: the router reads
    the token's own features (an identity), and every token's features
    are large on the experts it is to choose, distinct so that no two
    tie."""
    ks = jax.random.split(jax.random.key(seed), 5)
    x = onp.array(0.1 * jax.random.normal(ks[0], (TOKENS, WIDTH)))
    left = rows
    for t in range(TOKENS):
        here = min(CHOICES, left)
        left -= here
        # the held experts first, then experts held elsewhere
        chosen = list(range(here)) + [HELD + (t + j) % (ROUTED - HELD)
                                      for j in range(CHOICES - here)]
        x[t, chosen] = 3.0 + 0.25 * onp.arange(CHOICES)
    assert left == 0
    return {"x": jnp.asarray(x),
            "router_weight": 2.0 * jnp.eye(ROUTED, WIDTH),
            "w_gate": 0.3 * jax.random.normal(ks[1], (HELD, WIDTH, 8)),
            "w_up": 0.3 * jax.random.normal(ks[2], (HELD, WIDTH, 8)),
            "w_down": 0.3 * jax.random.normal(ks[3], (HELD, 8, WIDTH))}


LEAVES = ("x", "router_weight", "w_gate", "w_up", "w_down")
SIZES = {"num_experts_per_tok": CHOICES, "moe_routed_scaling_factor": 2.5}


def _program(p, probe):
    out = moe.routed_experts(*(p[name] for name in LEAVES), k=CHOICES,
                             held_start=0, num_held=HELD, scale=2.5)
    return jnp.sum(out * probe), out


def _reference(p, probe):
    out, _ = laguna._experts(SIZES, p["x"], p, correctness.Rounding,
                             held=(0, HELD))
    return jnp.sum(out * probe), out


@pytest.mark.parametrize("rows", [BUFFER - 1, BUFFER, BUFFER + 1,
                                  TOKENS * CHOICES],
                         ids=["one-under", "full", "one-over", "worst"])
def test_rows_past_the_buffer_are_computed_not_dropped(rows):
    """4 of 32 experts held, 4 choices a token: the buffer holds 128 of
    the 192 rows. With one row under, exactly, one row over and every
    row routed here the output and the gradients (input, router, the
    three expert matrices) are the plain reference's, and the gauges
    read what the case built."""
    from mxnet_tpu.telemetry import metrics
    assert BUFFER == 128 < TOKENS * CHOICES
    p = _routing_this_many_rows_here(rows)
    counts, _ = moe.routing_counts(p["x"], p["router_weight"], k=CHOICES,
                                   held_start=0, num_held=HELD)
    assert int(counts.sum()) == rows
    probe = jax.random.normal(jax.random.key(11), (TOKENS, WIDTH))
    (_, got), grads = jax.value_and_grad(_program, has_aux=True)(p, probe)
    (_, want), wanted = jax.value_and_grad(_reference, has_aux=True)(
        p, probe)
    assert onp.allclose(got, want, atol=1e-5)
    for name in LEAVES:
        assert onp.allclose(grads[name], wanted[name], atol=2e-5), name
    assert float(jnp.abs(wanted["router_weight"]).max()) > 1e-3

    blk = moe.RoutedExpertsFFN(WIDTH, 8, ROUTED, CHOICES, range(HELD), 2.5,
                               label=f"test.past.{rows}")
    blk.initialize()
    for name in LEAVES[1:]:
        getattr(blk, name).set_data(_wrap(p[name]))
    assert onp.allclose(blk(_wrap(p["x"]))._data, want, atol=1e-5)

    def gauge(name):
        return metrics.gauge(f"{name}.test.past.{rows}").value()

    assert gauge("moe_rows_routed") == rows
    assert gauge("moe_buffer_rows") == BUFFER
    assert gauge("moe_rows_overflow") == max(0, rows - BUFFER)
    assert gauge("moe_rows_dropped") == 0


def test_the_buffer_is_twice_the_expected_rows_in_whole_tiles():
    # the cell: 8192 tokens x 8 choices, 32 of 256 experts held
    assert moe.buffer_rows(8192 * 8, 32, 256) == 16384
    # every expert held, or twice the expected rows past the worst case
    assert moe.buffer_rows(40 * 3, 8, 8) == 120
    assert moe.buffer_rows(48 * 3, 4, 8) == 144
    # whole row tiles
    assert moe.buffer_rows(1000 * 8, 3, 64) == 768
