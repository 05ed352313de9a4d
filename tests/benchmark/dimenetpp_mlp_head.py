"""The plain reference of the tests' throw-away configuration WITHOUT
BatchNorm (`bench_testlib.NO_BATCHNORM`): `dimenetpp-s2ef`'s blocks, read
out through the stack's MLP node head on the last encoder block's node
features, summed per structure. `DIMEStack` has identity feature layers,
so such a model has no BatchNorm anywhere. No configuration of the
benchmark names this read-out, so it lives beside the tests, which hand it
to the harness as `benchmark.reference.dimenetpp_mlp_head`; a configuration
that does brings its reference under benchmark/reference/."""
import jax
import jax.numpy as jnp

from benchmark.reference import common, dimenetpp_s2ef


def node_energies(arch):
    def fn(variables, struct, pos, train):
        params = variables["params"]
        block = dimenetpp_s2ef.block_on(arch, struct, pos)
        x = jnp.asarray(struct["x"])
        for i in range(int(arch["num_conv_layers"])):
            x = jax.nn.relu(block(params[f"conv_{i}"], x))
        return common.mlp(params["head_0"]["MLP_0"], x, jax.nn.relu)[:, 0]
    return fn
