"""The routed layers' counters (telemetry/expert_load.py) and the scalars
the step hands them (models/decoder.expert_scalars): `moe_l<L>_windows` is
the trip count of the layer's loop over windows (ops/moe.live_windows), one
a layer pass at an even load and as many as the held pairs fill beyond
that."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bert_pytorch_tpu.config import Lfm2MoeConfig
from bert_pytorch_tpu.models import decoder
from bert_pytorch_tpu.ops import moe as moe_ops
from bert_pytorch_tpu.telemetry.expert_load import ExpertLoadCounters

from test_lfm2_moe import TOY

# 4 held of 16 experts, 2 selections a token: 512 tokens make 1,024 pairs,
# a window holds twice the even share, 512 of them
CFG = Lfm2MoeConfig.from_dict(dict(TOY, experts_total=16))
TOKENS = 512


@pytest.mark.parametrize("load, windows", [
    ([64, 64, 64, 64], 1),          # the even share
    ([0, 0, 0, 0], 1),              # nothing held: the first window runs
    ([128, 128, 128, 128], 1),      # twice the even share: a window, full
    ([128, 129, 128, 128], 2),      # one pair more
    ([0, 512, 512, 0], 2),          # every token to two held experts
], ids=["even", "none", "full", "one_more", "all_held"])
def test_windows_scalar_is_the_loops_trip_count(load, windows):
    assert decoder.routed_window_rows(CFG, TOKENS) == 512
    load = jnp.asarray([load, [64, 64, 64, 64]], jnp.int32)
    scalars = jax.jit(lambda load: decoder.expert_scalars(
        CFG, jnp.int32(7), TOKENS, load, jnp.zeros((2,), jnp.int32)))(load)
    assert int(scalars["moe_l0_windows"]) == windows
    assert int(scalars["moe_l1_windows"]) == 1
    assert int(scalars["moe_pairs_routed"]) == 2 * TOKENS
    assert int(scalars["moe_l0_e1"]) == int(load[0, 1])


@pytest.mark.parametrize("bias, windows", [({}, 1), ({5: 10.0, 4: 5.0}, 2)],
                         ids=["even", "every_token_to_held_experts"])
def test_the_scalar_counts_what_the_layer_ran(bias, windows):
    """The layer's own routing: the counter computed from the load the layer
    returns is the number of windows its loop computed pairs in."""
    x = jax.random.normal(jax.random.PRNGKey(0), (TOKENS, 64), jnp.float32)
    kernel = jax.random.normal(jax.random.PRNGKey(1), (64, 16)) * 0.1
    b = jnp.zeros((16,))
    for expert, value in bias.items():
        b = b.at[expert].set(value)
    w = jax.random.normal(jax.random.PRNGKey(2), (3, 4, 64, 32)) * 0.1
    rows = decoder.routed_window_rows(CFG, TOKENS)
    _, load, dropped = moe_ops.held_experts(
        x, moe_ops.route(x, kernel, b, 2, True, 1.0), w[0], w[1],
        w[2].transpose(0, 2, 1), CFG.held_range, rows)
    scalars = decoder.expert_scalars(CFG, jnp.int32(0), TOKENS, load[None],
                                      dropped[None])
    assert int(dropped) == 0
    assert int(scalars["moe_l0_windows"]) == windows == -(
        -max(int(jnp.sum(load)), 1) // rows)


def test_counters_sum_the_windows_of_every_step():
    counters = ExpertLoadCounters()
    step = {"moe_l0_e0": 3, "moe_l0_e1": 5, "moe_l0_dropped": 0,
            "moe_l0_windows": 2, "moe_l1_e0": 4, "moe_l1_e1": 4,
            "moe_l1_dropped": 0, "moe_l1_windows": 3,
            "moe_pairs_routed": 32, "step_loss": 1.0}
    counters.update(step)
    counters.update(dict(step, moe_l0_windows=5))
    fields = counters.fields()
    assert fields["moe_l0_windows"] == 7 and fields["moe_l1_windows"] == 6
    assert fields["moe_l0_pairs"] == 16 and fields["moe_l0_dropped"] == 0
    assert fields["moe_l0_load_max"] == 10
    assert fields["moe_l1_held_share"] == pytest.approx(16 / 64)
    assert np.isfinite(list(fields.values())).all()
