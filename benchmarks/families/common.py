"""What the job families share: the seeded key, leaf norms on the device and
the optimizer built from a configuration's ``optimizer`` entry."""

import types


def seed_key(seed):
    """A PRNG key from any whole number up to a little over 2**31 (more than
    a signed 32-bit integer holds)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def small_seed(seed):
    """``seed`` folded into the range every ``seed=`` argument of the
    program's pipelines takes."""
    return int(seed) % 2147483629


def leaf_norms(tree, other=None, scale=1.0):
    """``{"a/b/c": norm}`` of every leaf of ``scale * (tree - other)``, as
    device scalars (call under jit)."""
    import jax
    import jax.numpy as jnp

    if other is not None:
        tree = jax.tree.map(jnp.subtract, tree, other)
    return {
        "/".join(str(p.key) for p in path): scale * jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def to_floats(norms):
    import jax

    return {k: float(v) for k, v in jax.device_get(norms).items()}


def make_optimizer(opt):
    """``(optax transformation, first_gradient(opt_state) -> tree)``: the
    gradient the optimizer got in its first step, read back from its state
    after that step."""
    import optax

    if opt["name"] == "adamw":
        tx = optax.adamw(opt["learning_rate"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                         weight_decay=opt["weight_decay"])
        return tx, lambda state: (state[0].mu, 1.0 / (1.0 - opt["b1"]))
    if opt["name"] == "sgd":
        tx = optax.sgd(opt["learning_rate"], momentum=opt["momentum"])
        return tx, lambda state: (state[0].trace, 1.0)
    raise ValueError("unknown optimizer {!r}".format(opt["name"]))


def seeded_state(strategy, optimizer, init_params, key):
    """A ``TrainState`` on the mesh whose parameters are ``init_params(key)``.

    ``create_state`` closes over its init arguments, so a key handed to it
    becomes a constant of the init program and every new seed compiles that
    program again (20-30 s on the chip). So it is given a seedless init of the
    same shapes — one program, found in the compile cache by every run — and
    the seeded weights come from one jitted call that takes the key as an
    argument, placed with the state's own shardings. The optimizer's initial
    state (zero moments) does not depend on the weights."""
    import jax
    import jax.numpy as jnp

    shapes = jax.eval_shape(init_params, key)
    state = strategy.create_state(
        lambda: jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype), shapes), optimizer)
    target = {"params": state.params, **state.model_state} if state.model_state else {"params": state.params}
    shardings = jax.tree.map(lambda x: x.sharding, target)
    seeded = jax.jit(init_params, out_shardings=shardings)(key)
    return state.replace(
        params=seeded["params"], model_state={k: v for k, v in seeded.items() if k != "params"})


def norm_readers(first_gradient, init_params, key):
    """``(first_grad(state), param_change(state))``. The first: per-leaf norms
    and per-leaf sketches (``sketch.py``, signs from ``key``) of the gradient
    the optimizer got in step one. The second: per-leaf norms of the
    parameters' distance from their seeded start. All as host floats."""
    import jax

    from benchmarks import sketch

    def grad_readings(opt_state, k):
        tree, scale = first_gradient(opt_state)
        named = {"/".join(str(p.key) for p in path): scale * leaf
                 for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}
        return leaf_norms(tree, scale=scale), {n: sketch.leaf_sketch(x, k, n) for n, x in named.items()}

    grad_readings = jax.jit(grad_readings)
    change_norms = jax.jit(lambda params, k: leaf_norms(params, init_params(k)))

    def first_grad(state):
        norms, sketches = jax.device_get(grad_readings(state.opt_state, key))
        return {k: float(v) for k, v in norms.items()}, {k: v.tolist() for k, v in sketches.items()}

    return first_grad, lambda s: to_floats(change_norms(s.params, key))


job = types.SimpleNamespace
