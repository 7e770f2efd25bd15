"""What the tests of a block's rematerialisation share: the matrix products in the gradient of ONE block of a toy
model, counted as `dot_general` equations of its jaxpr (sub-jaxprs too: the rematerialised forward pass is one)."""
import jax
import jax.numpy as jnp
from flax import nnx


def count_eqns(jaxpr, primitive: str) -> int:
    """Equations of `primitive` in a jaxpr and in every jaxpr its equations hold."""
    return sum((eqn.primitive.name == primitive) + sum(count_eqns(sub, primitive) for sub in jax.core.jaxprs_in_params(eqn.params))
               for eqn in jaxpr.eqns)


def block_grad_dots(model, index: int, seq_len: int, names=None) -> int:
    """`dot_general` equations in the gradient (every parameter and the input) of block `index` over (1, seq_len, dim):
    through the model's own `_run_block` with rematerialisation on or, given `names`, with the block under
    `save_only_these_names(*names)`."""
    args = (model._rope(seq_len),) if hasattr(model, '_rope') else ()
    graphdef, state, rest = nnx.split(model, nnx.Param, ...)

    def loss(state, x):
        m = nnx.merge(graphdef, state, rest, copy=True)
        m.set_grad_checkpointing(True)
        if names is None:
            y, _ = m._run_block(m.blocks[index], x, *args)
        else:
            policy = jax.checkpoint_policies.save_only_these_names(*names)
            y, _ = nnx.remat(lambda b, x, *a: b(x, *a), policy=policy)(m.blocks[index], x, *args)
        return jnp.sum(jnp.square(y.astype(jnp.float32)))
    x = jnp.full((1, seq_len, model.embed_dim), 0.1, jnp.float32)
    return count_eqns(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(state, x).jaxpr, 'dot_general')
