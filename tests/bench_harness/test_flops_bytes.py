"""The Llama family's counts (operations and bytes) against values worked
out by hand."""
import json
import os

import pytest

from benchmarks import loader

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
flops = hbm = loader.Manifest(REPO).family("llama").counts


def sizes(name):
    return json.load(open(os.path.join(REPO, "benchmarks", "configs", name + ".json")))


ATTN = 4096 * 4096 * 2 + 4096 * 1024 * 2  # wq, wo; wk, wv (8 KV heads of 128)
MLP = 3 * 4096 * 14336


def test_dense_layer_by_hand():
    assert ATTN == 41_943_040 and MLP == 176_160_768
    assert flops.layer_matmul_params(sizes("mistral-7b-d4")) == ATTN + MLP == 218_103_808


@pytest.mark.parametrize("name,layers,vocab", [("mistral-7b-d4", 4, 32768), ("mistral-7b-d16", 16, 32768)])
def test_matmul_params_leave_out_the_embedding(name, layers, vocab):
    assert flops.matmul_params(sizes(name)) == layers * (ATTN + MLP) + 4096 * vocab


def test_train_flops_per_token_mistral_d4():
    # 6 x (4 layers x 218.1 M + 134.2 M of head) + 6 x 4 x 4096 x 4096 of causal attention
    want = 6 * (4 * 218_103_808 + 134_217_728) + 6 * 4 * 4096 * 4096
    assert flops.train_flops_per_token(sizes("mistral-7b-d4"), 4096) == want
    assert want == pytest.approx(6.44e9, rel=2e-3)  # 105 TFLOP a step of 16,384 tokens
    assert want * 16384 == pytest.approx(105.6e12, rel=2e-3)


def test_moe_counts_routed_experts_for_flops_and_all_for_bytes():
    s = sizes("mixtral-8x7b-d4")
    active = ATTN + 4096 * 8 + 2 * MLP
    every = ATTN + 4096 * 8 + 8 * MLP
    assert flops.layer_matmul_params(s) == active
    assert flops.layer_matmul_params(s, active_only=False) == every == 1_451_261_952
    assert hbm.weight_bytes(s) == 2 * (4 * every + 4096 * 32000)
    assert hbm.weight_bytes(s) == pytest.approx(11.87e9, rel=1e-3)


def test_prefill_flops_of_a_padded_prompt():
    s = sizes("mistral-7b-d16")
    want = 2 * (16 * 218_103_808 + 134_217_728) * 2048 + 2 * 16 * 2048 * 2048 * 4096
    assert flops.forward_flops(s, 2048) == want == pytest.approx(15.4e12, rel=1e-2)
    assert flops.forward_flops(sizes("mixtral-8x7b-d4"), 2048, active_only=False) == pytest.approx(24.6e12, rel=1e-2)


@pytest.mark.parametrize("name,per_token", [("mistral-7b-d4", 16384), ("mistral-7b-d16", 65536),
                                            ("mixtral-8x7b-d4", 16384)])
def test_cache_bytes_per_token(name, per_token):
    # 2 (K and V) x layers x 8 KV heads x 128 x 2 bytes
    assert hbm.cache_bytes_per_token(sizes(name)) == per_token


def test_decode_tick_bytes_mistral_d16():
    s = sizes("mistral-7b-d16")
    weights = 2 * (16 * 218_103_808 + 134_217_728)
    assert hbm.weight_bytes(s) == weights == pytest.approx(7.25e9, rel=1e-3)
    assert hbm.decode_tick_bytes(s, 8000) == weights + 8000 * 65536
