"""A Kanana-2-shaped ``TransformerLM`` (latent attention without a query
bottleneck, a held eighth of sigmoid-routed experts with two shared, one
leading dense layer) on packed rows, at toy widths on the CPU, against the
plain float32 reference (``benchmark/reference/kanana-2-30b-a3b.py``, which
imports nothing of ``bluefog_tpu``).  The whole-model cases are
``tests/twins.py``'s on the twin ``tiny-kanana2`` (every row of its batches
holds four documents); here beside them: a packed row gives each document
the logits it gets alone, the blocks that cannot keep documents apart
raise, the published widths count 687,502,336 parameters, and the task's
sample for the model check."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark import spec  # noqa: E402
from bluefog_tpu import models  # noqa: E402
from bluefog_tpu.data import document_layout  # noqa: E402
import twins  # noqa: E402
from twins import (  # noqa: E402,F401
    HIGHEST, test_atc_on_four_devices_is_w_times_the_handwritten_update,
    test_float8_rounded_matrices_fail_the_bounds,
    test_the_shares_add_up_to_the_uncut_layer,
    test_toy_model_in_bfloat16_is_inside_the_twin_bounds,
    test_toy_model_loss_and_every_gradient_leaf_in_float32, with_dtype)

TWINS = ("tiny-kanana2",)
CELL = "kanana2-packed-s8192-1chip"


def test_the_twin_holds_an_eighth_in_eight_shares():
    """``test_the_shares_add_up_to_the_uncut_layer`` cuts the router's
    width into shares of the experts the twin holds: eight of them, as
    eight chips share the cell's layer."""
    config, _, _ = twins.load("tiny-kanana2")
    cell = spec.load_cell(CELL).config
    for c in (config, cell):
        assert c["router_width"] == 8 * c["n_routed_experts"]
        assert c["n_shared_experts"] == 2 and c["q_lora_rank"] is None
        assert c["first_k_dense_replace"] == 1


@pytest.mark.parametrize("attention", ["flash", "local"])
def test_a_packed_row_gives_each_document_the_logits_it_gets_alone(attention):
    """Dropless routing is per token and attention keeps to a token's own
    document, so a document's logits do not depend on its neighbours in the
    row: exact to float32 rounding, through the flash kernels' document
    mask and through the plain ``jnp`` fallback alike."""
    config, task, _ = twins.load("tiny-kanana2")
    config = with_dtype(config, "float32")
    config["model"]["attention"] = attention
    model = task.make_model(config)
    lengths = [70, 3, 91, 36]
    key = jax.random.PRNGKey(5)
    tokens = jax.random.randint(key, (1, sum(lengths)), 0,
                                config["vocab_size"])
    params = model.init(key, tokens[:, :16])["params"]
    bias = 0.05 * jax.random.normal(key, (config["router_width"],))
    state = {f"block_{i}": {"moe": {"bias": bias}}
             for i in task.expert_layers(config)}
    segment_ids, positions = document_layout(lengths)
    apply = jax.jit(lambda t, **kw: model.apply(
        {"params": params, "router_state": state}, t, **kw))
    with HIGHEST():
        packed = apply(tokens, positions=positions[None],
                       segment_ids=segment_ids[None])
        start = 0
        for n in lengths:
            alone = apply(tokens[:, start:start + n])
            np.testing.assert_allclose(packed[:, start:start + n], alone,
                                       rtol=2e-5, atol=2e-5)
            start += n
        # and without the ids the row is one document: other logits
        whole = apply(tokens)
    assert float(jnp.abs(whole - packed)[:, lengths[0]:].max()) > 1e-2
    np.testing.assert_allclose(whole[:, :lengths[0]],
                               packed[:, :lengths[0]], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kind", ["conv", "mamba", "sliding_attention"])
def test_a_block_that_cannot_keep_documents_apart_raises(kind):
    cfg = models.TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=4, embed_dim=32,
        pos_encoding="rope", mlp="swiglu", sliding_window=8,
        layer_types=("full_attention", kind), ssm_heads=4, ssm_head_dim=16,
        ssm_state=16, ssm_chunk=16, num_kv_heads=4)
    model = models.TransformerLM(cfg)
    tokens = jnp.zeros((1, 32), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    segment_ids, positions = document_layout([20, 12])
    model.apply(variables, tokens)          # without ids it runs
    with pytest.raises(NotImplementedError, match=f"'{kind}' block"):
        model.apply(variables, tokens, positions=positions[None],
                    segment_ids=segment_ids[None])


def test_a_decode_cache_takes_no_documents():
    cfg = models.TransformerConfig(vocab_size=64, num_layers=1, num_heads=2,
                                   embed_dim=16, pos_encoding="rope")
    model = models.TransformerLM(cfg)
    tokens = jnp.zeros((1, 1), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    from bluefog_tpu.models.transformer import init_cache
    with pytest.raises(NotImplementedError, match="packed row"):
        model.apply(variables, tokens, positions=jnp.zeros((1, 1), int),
                    cache=init_cache(cfg, 1, 8),
                    segment_ids=jnp.zeros((1, 1), jnp.int32))


def test_the_published_widths_count_687502336_parameters():
    """``jax.eval_shape`` of the cell's own model: the issue's arithmetic,
    part by part."""
    cell = spec.load_cell(CELL)
    task = spec.task_module(cell)
    model = task.make_model(cell.config)
    params, aux = jax.eval_shape(
        lambda key: task.init(model, key, cell.config,
                              cell.traffic["batch"]), jax.random.PRNGKey(0))
    count = lambda tree: sum(int(np.prod(x.shape))
                             for x in jax.tree.leaves(tree))
    assert count(params["block_0"]["mla"]) == 26_345_984
    assert count(params["block_0"]) == 64_098_816
    assert count(params["block_1"]) == 111_546_880
    assert count(params["block_1"]["moe"]["gate"]) == 16 * 2048 * 768
    assert count(params["wte"]) + count(params["lm_head"]) == 65_667_072
    assert count(params) == 687_502_336
    assert all(x.dtype == jnp.float32 for x in jax.tree.leaves(params))
    assert aux["bias"].shape == aux["load"].shape == (5, 128)


def test_the_model_checks_sample_is_the_rows_tail_as_the_packer_lays_it():
    cell = spec.load_cell(CELL)
    task = spec.task_module(cell)
    batch = cell.traffic["batch"]
    assert batch["documents"] == [2961, 1734, 1207, 811, 562, 377, 243, 161,
                                  89, 47]
    assert sum(batch["documents"]) == batch["seq_len"] == 8192
    assert all(sum(batch["documents"][:i]) % 128 for i in range(1, 10))
    sample = task.check_batch(batch)
    assert sample == {"sequences": 1, "seq_len": 1024,
                      "documents": [107, 377, 243, 161, 89, 47]}
    tokens, segment_ids, positions = jax.eval_shape(
        lambda key: task.make_batch(key, cell.config, sample),
        jax.random.PRNGKey(0))
    assert tokens.shape == segment_ids.shape == positions.shape == (1, 1024)
    _, ids, at = task.make_batch(jax.random.PRNGKey(0), cell.config, sample)
    assert ids[0, 106] == 0 and ids[0, 107] == 1 and at[0, 107] == 0
    assert int(ids[0, -1]) == 5 and int(at[0, -1]) == 46
    with pytest.raises(AssertionError):
        task.check_batch({"seq_len": 8192, "documents": [7168, 1024]})
