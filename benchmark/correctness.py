"""What decides ``correct`` in a training cell.

The timed step object is driven from the seed through its first three
steps before the window (``ProgramReadings`` collects, from that very
object: each step's mean loss, the norm of every leaf of the first
gradient as the optimizer got it, worked out of the optimizer's state
after one step, and the norm of every leaf's change after the three).
Once the window has closed and the program's state is freed, the plain
reference follows the same three steps from the same weights and
batches (``reference_follow``), and ``compare`` sets the two side by
side, each number against a limit of its own:

- ``loss_gap``: the widest relative gap of a step's mean loss;
- ``grad_gap``: over the leaves, the widest gap between the program's
  norm of the first gradient and the reference's, against the
  reference's norm of that leaf or of the median leaf, whichever is
  larger (some gradients are all but zero);
- ``delta_gap``: the same for the norm of the parameters' change after
  three steps. Leaves whose first gradient in the reference is under a
  thousandth of the median leaf's are left out of this one (a bias that
  BatchNorm or softmax cancels moves by round-off alone).

It is the gap between two norms, not the norm of a difference. The
reference computes in float32 at ``highest`` matmul precision and keeps
parameters and optimizer state in the dtype the configuration states
they are stored in (rounding once, at the store). Its control is the
same reference with its arithmetic one precision down (every array in
``bf16`` under a float32 configuration; ``fp8`` arrays, e4m3 forward and
e5m2 backward, under a bfloat16 one); the faults
(``half_batch``, ``state_unchanged``) are the reference put in the
program's place with the fault planted. Neither runs in a benchmark
run: ``benchmark/calibrate.py`` and the tests run them.
"""
import functools
import importlib
import json
import math
import statistics

import jax
import jax.numpy as jnp

N_STEPS = 3
ZERO_GRAD_SHARE = 1e-3  # of the median leaf's gradient norm


# ---------------------------------------------------------------------------
# the steps' random keys, as the configuration states the program draws
# them (``assumed.rng``): what a step's dropout masks are made from
# ---------------------------------------------------------------------------

def program_seed(seed):
    """What set-up hands to the program's ``random.seed`` before the
    first step: ``--seed`` brought into the range a key seed holds."""
    return int(seed) % (2 ** 31 - 1)


def step_keys(seed):
    """The key of each of the first ``N_STEPS`` steps after
    ``random.seed(program_seed(seed))``: the program keeps one running
    key, splits it in two before every step, keeps the first half and
    gives the step the second. Plain ``jax.random``; nothing of the
    program is asked."""
    running = jax.random.key(program_seed(seed))
    keys = []
    for _ in range(N_STEPS):
        running, step = jax.random.split(running)
        keys.append(step)
    return keys


# ---------------------------------------------------------------------------
# the optimizers as the configuration states them (MXNet's forms)
# ---------------------------------------------------------------------------

def opt_init(opt, w):
    if opt["name"] == "sgd":
        return jnp.zeros_like(w)
    if opt["name"] == "adam":
        return (jnp.zeros_like(w), jnp.zeros_like(w))
    raise ValueError(f"no reference for optimizer {opt['name']!r}")


def step_rate(opt, t):
    """The rate of update ``t`` (counting from 1), worked out on the
    host: MXNet's Adam folds both bias corrections into it."""
    lr = opt["learning_rate"]
    if opt["name"] == "adam":
        lr *= math.sqrt(1 - opt["beta2"] ** t) / (1 - opt["beta1"] ** t)
    return lr


def opt_update(opt, lr_t, w, g, state):
    """One update of one leaf in float32 at the rate ``step_rate``
    gives. Returns the new weight and state, both float32 (the caller
    stores them)."""
    f32 = jnp.float32
    w32 = w.astype(f32)
    g = g.astype(f32) + opt.get("wd", 0.0) * w32
    if opt["name"] == "sgd":
        mom = opt["momentum"] * state.astype(f32) - lr_t * g
        return w32 + mom, mom
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["epsilon"]
    mean = b1 * state[0].astype(f32) + (1 - b1) * g
    var = b2 * state[1].astype(f32) + (1 - b2) * jnp.square(g)
    return w32 - lr_t * mean / (jnp.sqrt(var) + eps), (mean, var)


def first_grad_scale(opt):
    """By what to multiply the norm of the optimizer's first-moment
    state after ONE step to get the norm of the gradient it was given."""
    if opt["name"] == "sgd":
        return 1.0 / opt["learning_rate"]  # mom_1 = -lr * g_1
    if opt["name"] == "adam":
        return 1.0 / (1.0 - opt["beta1"])  # mean_1 = (1 - beta1) * g_1
    raise ValueError(f"no reference for optimizer {opt['name']!r}")


def first_moment(opt, state):
    return state if opt["name"] == "sgd" else state[0]


@jax.jit
def _norms(leaves):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
                      for v in leaves])


@jax.jit
def _diff_norms(new, old):
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                    - b.astype(jnp.float32))))
        for a, b in zip(new, old)])


def leaf_norms(names, tree):
    vals = jax.device_get(_norms([tree[n] for n in names]))
    return {n: float(v) for n, v in zip(names, vals)}


def diff_norms(names, new, old):
    vals = jax.device_get(_diff_norms([new[n] for n in names],
                                      [old[n] for n in names]))
    return {n: float(v) for n, v in zip(names, vals)}


# ---------------------------------------------------------------------------
# the program's side: readings off the timed step object
# ---------------------------------------------------------------------------

class ProgramReadings:
    """Collects the program's numbers while set-up drives the timed step
    object through its first ``N_STEPS`` steps. It reads the net's
    parameters and the trainer's optimizer state; it changes nothing."""

    def __init__(self, opt, net, trainer, trainable):
        self.opt, self.net, self.trainer = opt, net, trainer
        self.names = sorted(trainable)
        self.losses, self.grad_norms, self.delta_norms = [], None, None

    def _params(self):
        params = self.net._collect_params_with_prefix()
        return {n: params[n].data()._data for n in self.names}

    def _first_moments(self):
        params = self.net._collect_params_with_prefix()
        states = self.trainer._updaters[0].states
        index_of = self.trainer._param2idx
        out = {}
        for n in self.names:
            st = states[index_of[params[n].name]]
            st = st[0] if isinstance(st, (tuple, list)) else st
            out[n] = st._data
        return out

    def after_step(self, loss, initial_weights=None):
        """``loss`` is the step's per-sample loss vector (a jax array).
        After the last step pass the weights the net started from."""
        self.losses.append(float(jnp.mean(loss.astype(jnp.float32))))
        if len(self.losses) == 1:
            scale = first_grad_scale(self.opt)
            self.grad_norms = {
                n: v * scale for n, v in
                leaf_norms(self.names, self._first_moments()).items()}
        if len(self.losses) == N_STEPS:
            self.delta_norms = diff_norms(self.names, self._params(),
                                          initial_weights)

    def readings(self):
        return {"losses": self.losses, "grad_norms": self.grad_norms,
                "delta_norms": self.delta_norms}


# ---------------------------------------------------------------------------
# the reference's side
# ---------------------------------------------------------------------------

class Rounding:
    """What a family's reference calls around its arithmetic: ``inp`` on
    each operand of a convolution or matrix product, ``out`` on the
    product's result, ``act`` on every other array it keeps between
    layers. The reference itself rounds nothing."""

    @staticmethod
    def inp(a):
        return a

    @staticmethod
    def out(a):
        return a

    @staticmethod
    def act(a):
        return a


def _scaled_round(a, dtype):
    """Round to an 8-bit float with one scale a tensor: its largest
    magnitude maps to the format's largest."""
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / top
    return (a / scale).astype(dtype).astype(a.dtype) * scale


@jax.custom_vjp
def _fp8_operand(a):
    return _scaled_round(a, jnp.float8_e4m3fn)


_fp8_operand.defvjp(lambda a: (_fp8_operand(a), None), lambda _, g: (g,))


@jax.custom_vjp
def _fp8_stored(a):
    return _scaled_round(a, jnp.float8_e4m3fn)


_fp8_stored.defvjp(lambda a: (_fp8_stored(a), None),
                   lambda _, g: (_scaled_round(g, jnp.float8_e5m2),))


class Fp8Rounding(Rounding):
    """The bfloat16 policy with float8 in bfloat16's place, as float8
    training is done: every operand of a product and every array kept
    between layers rounded to e4m3 on the way forward, every gradient
    that flows back through a kept array rounded to e5m2, each with one
    scale a tensor. The weights' own gradients, BatchNorm's leaves and
    statistics and the loss stay as the policy keeps them."""
    inp = staticmethod(_fp8_operand)
    out = staticmethod(_fp8_stored)
    act = staticmethod(_fp8_stored)


def reference_step(family, sizes, opt, trainable, precision, fault=None):
    """The reference's whole training step as a pure function
    ``(params, state, x, y, rate, key) -> (params, state, mean loss,
    grads)``; ``key`` is the step's random key (``step_keys``), which a
    family with dropout draws its masks from.

    ``precision``: ``"reference"`` (float32), or a control's: ``"bf16"``
    (every array and product in bfloat16) or ``"fp8"`` (``Fp8Rounding``
    on every array the bfloat16 policy keeps in bfloat16). ``fault``:
    ``"half_batch"`` leaves the second half of the batch out and takes
    the mean over the rest; ``"state_unchanged"`` returns parameters and
    state as they came."""
    compute = jnp.bfloat16 if precision == "bf16" else jnp.float32
    q = Fp8Rounding if precision == "fp8" else Rounding

    def loss_of(tvals, others, x, y, key):
        p = {n: v.astype(compute) for n, v in {**others, **tvals}.items()}
        loss, stats = family.reference_loss(sizes, p, x, y, q, key)
        # the step seeds every sample's loss with one and rescales by
        # the batch: the gradient of the mean
        return jnp.mean(loss.astype(jnp.float32)), stats

    def step(params, state, x, y, lr_t, key):
        if fault == "half_batch":
            x, y = x[:x.shape[0] // 2], y[:y.shape[0] // 2]
        tvals = {n: params[n] for n in trainable}
        others = {n: v for n, v in params.items() if n not in tvals}
        (loss, stats), grads = jax.value_and_grad(
            loss_of, has_aux=True)(tvals, others, x, y, key)
        if fault == "state_unchanged":
            return params, state, loss, grads
        new_p, new_s = dict(params), {}
        for n in trainable:
            w, s = opt_update(opt, lr_t, params[n], grads[n], state[n])
            new_p[n] = w.astype(params[n].dtype)
            new_s[n] = jax.tree.map(
                lambda v, n=n: v.astype(params[n].dtype), s)
        for n, v in stats.items():
            new_p[n] = v.astype(params[n].dtype)
        return new_p, new_s, loss, grads

    return step


@functools.lru_cache(maxsize=None)
def _jitted_step(family_name, sizes_json, opt_json, trainable, precision,
                 fault):
    """One jitted ``reference_step`` for each distinct set of arguments,
    so that following several seeds traces and compiles it once."""
    return jax.jit(reference_step(
        importlib.import_module(family_name), json.loads(sizes_json),
        json.loads(opt_json), list(trainable), precision, fault))


def reference_follow(family, sizes, opt, weights, batches, keys,
                     precision, fault=None):
    """The same readings as ``ProgramReadings`` gives, from the plain
    reference (``reference_step``) started at ``weights`` (leaves in
    their stored dtype) and fed ``batches[i]`` under ``keys[i]``
    (``step_keys``), a step a key. The reference itself runs at
    ``highest`` matmul precision, a control at the default."""
    trainable = sorted(n for n in weights if not family.is_state(n))
    matmul = "highest" if precision == "reference" else "default"
    step = _jitted_step(family.__name__, json.dumps(sizes, sort_keys=True),
                        json.dumps(opt, sort_keys=True), tuple(trainable),
                        precision, fault)
    params = dict(weights)
    state = {n: opt_init(opt, params[n]) for n in trainable}
    losses, grad_norms = [], None
    with jax.default_matmul_precision(matmul):
        for i, key in enumerate(keys):
            x, y = batches[i]
            params, state, loss, grads = step(
                params, state, x, y, jnp.float32(step_rate(opt, i + 1)),
                key)
            losses.append(float(loss))
            if i == 0:
                if fault == "state_unchanged":
                    grad_norms = leaf_norms(trainable, grads)
                else:
                    scale = first_grad_scale(opt)
                    grad_norms = {
                        n: v * scale for n, v in leaf_norms(
                            trainable, {n: first_moment(opt, state[n])
                                        for n in trainable}).items()}
            del grads
    delta = diff_norms(trainable, params, weights)
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta}


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

def _leaf_gaps(got, ref, names):
    """Every leaf's gap between the two norms, against the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    floor = statistics.median(ref[n] for n in names)
    return {n: abs(got[n] - ref[n]) / max(ref[n], floor, 1e-30)
            for n in names}


def _reduce(gaps):
    """(worst, its leaf, median, mean) of the leaves' gaps; a gap that
    is not finite is the worst there is and spoils the others."""
    bad = [n for n, g in gaps.items() if not math.isfinite(g)]
    if bad:
        inf = float("inf")
        return inf, bad[0], inf, inf
    where = max(gaps, key=gaps.get)
    vals = list(gaps.values())
    return gaps[where], where, statistics.median(vals), \
        sum(vals) / len(vals)


def compare(got, ref, limits):
    """``(correct, compared, detail)``. Seven numbers are worked out;
    those the cell's ``limits`` name are ``compared``, each beside its
    limit under a short plain name, and decide ``correct``; the others
    are kept in ``detail["not_compared"]`` so that their readings are on
    record. A number that is not finite fails.

    ``loss_gap`` is the widest relative gap of a step's mean loss;
    ``grad_gap`` and ``delta_gap`` are the worst leaf's gap of the first
    gradient's norm and of the three steps' change; ``*_median`` and
    ``*_mean`` are the median and the mean leaf's, which the rounding
    noise of one small leaf does not move."""
    names = sorted(ref["grad_norms"])
    loss_gaps = [abs(a - b) / abs(b)
                 for a, b in zip(got["losses"], ref["losses"])]
    loss_gap = max(loss_gaps) if all(map(math.isfinite, loss_gaps)) \
        else float("inf")
    g_floor = ZERO_GRAD_SHARE * statistics.median(
        ref["grad_norms"][n] for n in names)
    moved = [n for n in names if ref["grad_norms"][n] >= g_floor]
    g_max, g_leaf, g_med, g_mean = _reduce(
        _leaf_gaps(got["grad_norms"], ref["grad_norms"], names))
    d_max, d_leaf, d_med, d_mean = _reduce(
        _leaf_gaps(got["delta_norms"], ref["delta_norms"], moved))
    numbers = {"loss_gap": loss_gap, "grad_gap": g_max,
               "delta_gap": d_max, "grad_gap_median": g_med,
               "delta_gap_median": d_med, "grad_gap_mean": g_mean,
               "delta_gap_mean": d_mean}
    unknown = set(limits) - set(numbers)
    if unknown:
        raise ValueError(f"limits on numbers nobody computes: {unknown}")
    compared = {k: {"value": float(v), "limit": limits[k]}
                for k, v in numbers.items() if k in limits}
    correct = bool(compared) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in compared.values())
    detail = {"losses_program": got["losses"],
              "losses_reference": ref["losses"],
              "grad_gap_leaf": g_leaf, "delta_gap_leaf": d_leaf,
              "leaves": len(names), "leaves_in_delta": len(moved),
              "not_compared": {k: float(v) for k, v in numbers.items()
                               if k not in limits}}
    return correct, compared, detail
