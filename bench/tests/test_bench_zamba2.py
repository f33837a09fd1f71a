"""The Zamba2 cell's run at a size a CPU test can hold: the cell's files
as they are, with the client cut to a small model of the same structure
(6 layers, hybrids at 2 and 5, ngroups 2, SSD chunks of 16) on 4 devices
of 2 sequences of 32 tokens, and the reference file configured alike.  A
sound run is correct; a run whose second adapter is left out of the
forward, or whose training loss reads half of each sequence's tokens,
is not.  The look for a chip is skipped.  The configuration's widths are
written once, in its file: the reference reads them there, and the
program's registry entry is checked against them."""
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

import _bench_path
import _faults
from harness import runner, spec
from repro.configs.base import (HybridConfig, ModelConfig, SSMConfig,
                                get_config, register)
from repro.fl.client import ZambaClient, make_client
from repro.sim import engine

WORKLOAD = "zamba2-static-n8"
register(ModelConfig(
    name="zamba2-bench-tiny", arch_type="hybrid", num_layers=6, d_model=64,
    num_heads=4, num_kv_heads=4, head_dim=32, d_ff=128, vocab_size=512,
    mlp_activation="gelu",
    ssm=SSMConfig(state_dim=16, head_dim=16, expand=2, conv_width=4,
                  chunk=16, ngroups=2),
    hybrid=HybridConfig(layer_ids=(2, 5), num_shared_blocks=2,
                        adapter_rank=8)))
SHAPE = dict(vocab=512, d=64, layers=6, hybrid_ids=(2, 5), shared_blocks=2,
             heads=4, head_dim=32, ff=128, rank=8, expand=2,
             ssm_head_dim=16, state=16, groups=2, conv=4, chunk=16)


@pytest.fixture(scope="module")
def ref():
    """The reference file configured to the small model, loaded once, so
    the module's runs share its compiled programs."""
    mod = spec.reference_module(spec.load_cell(_bench_path.ROOT, WORKLOAD))
    mod.configure(**SHAPE)
    yield mod
    mod.configure()


@pytest.fixture
def cell(monkeypatch, ref):
    c = spec.load_cell(_bench_path.ROOT, WORKLOAD)
    c.config.update(client="zamba2-bench-tiny", num_hidden_layers=0,
                    devices=4, samples_per_device=2, seq_len=32, lr=0.5)
    monkeypatch.setattr(runner, "reference_module", lambda cell: ref)
    return c


def _planted(monkeypatch, faulty):
    """The engine runs ``faulty``, a ZambaClient subclass: a client of
    another class is another static argument, so the program's phases
    are traced anew with the fault."""
    made = engine.make_client

    def make(name, **kw):
        c = made(name, **kw)
        return faulty(cfg=c.cfg, seq_len=c.seq_len)

    monkeypatch.setattr(engine, "make_client", make)


def test_a_sound_zamba2_run_is_correct(capsys, cell):
    res = _faults.result(capsys, cell)
    assert res["correct"] is True, res["checks"]
    assert set(res["checks"]) == {"param_gap", "solve_faults"}


class _NoSecondAdapter(ZambaClient):
    def hidden(self, frozen, adapters, x):
        gone = jax.tree_util.tree_map(jnp.zeros_like, adapters[1])
        return super().hidden(frozen, [adapters[0], gone], x)


class _FirstHalfOfTheTokens(ZambaClient):
    def loss(self, frozen, p, x, y):
        half = x.shape[-1] // 2
        logits = self.logits(frozen, p, x)[..., :half, :]
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, y[..., :half, None], axis=-1)
        return jnp.mean(logz - ll[..., 0])


def test_the_second_adapter_left_out_is_not_correct(capsys, cell,
                                                    monkeypatch):
    _planted(monkeypatch, _NoSecondAdapter)
    res = _faults.result(capsys, cell)
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["param_gap"]["value"] > \
        res["checks"]["param_gap"]["limit"]


def test_half_of_each_sequence_left_out_of_the_loss_is_not_correct(
        capsys, cell, monkeypatch):
    _planted(monkeypatch, _FirstHalfOfTheTokens)
    res = _faults.result(capsys, cell)
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["param_gap"]["value"] > \
        res["checks"]["param_gap"]["limit"]


def test_the_widths_are_the_configuration_files():
    c = spec.load_cell(_bench_path.ROOT, WORKLOAD)
    conf = c.config
    ref = spec.reference_module(c)
    assert ref.PUBLISHED == ref.published(os.path.join(
        _bench_path.ROOT, "bench", "configs", "zamba2-7b-stlf-n8.json"))
    with open(os.path.join(_bench_path.ROOT, "BENCHMARK.json")) as f:
        entry = next(e for e in json.load(f)["configs"]
                     if e["name"] == conf["name"])
    assert set(entry["reduced"]) == set(conf["reduced"])
    # the published depth, before the cut ("81 -> 12: ...")
    full, cut = map(int, re.match(r"(\d+) -> (\d+)",
                                  conf["reduced"]["num_hidden_layers"])
                    .groups())
    assert cut == conf["num_hidden_layers"]
    assert [i for i, t in enumerate(conf["layers_block_type"])
            if t == "hybrid"] == conf["hybrid_layer_ids"]
    assert len(conf["layers_block_type"]) == full
    reg = get_config(conf["client"])
    assert (reg.num_layers, reg.d_model, reg.vocab_size, reg.num_heads,
            reg.num_kv_heads, reg.resolved_head_dim(), reg.d_ff,
            reg.mlp_activation, reg.rope_theta, reg.norm_eps) == (
        full, conf["hidden_size"], conf["vocab_size"],
        conf["num_attention_heads"], conf["num_key_value_heads"],
        conf["attention_head_dim"], conf["ffn_hidden_size"],
        conf["hidden_act"], conf["rope_theta"], conf["rms_norm_eps"])
    assert reg.num_heads * reg.resolved_head_dim() == \
        conf["attention_hidden_size"] == 2 * conf["hidden_size"]
    assert (reg.ssm.state_dim, reg.ssm.head_dim, reg.ssm.expand,
            reg.ssm.conv_width, reg.ssm.chunk, reg.ssm.ngroups) == (
        conf["mamba_d_state"], conf["mamba_headdim"], conf["mamba_expand"],
        conf["mamba_d_conv"], conf["chunk_size"], conf["mamba_ngroups"])
    assert reg.ssm.expand * reg.d_model // reg.ssm.head_dim == \
        conf["n_mamba_heads"]
    assert (reg.hybrid.layer_ids, reg.hybrid.num_shared_blocks,
            reg.hybrid.adapter_rank) == (
        tuple(conf["hybrid_layer_ids"]), conf["num_mem_blocks"],
        conf["adapter_rank"])
    # what the cell runs: the program's client cut as the reference is
    client = make_client(conf["client"],
                         num_hidden_layers=conf["num_hidden_layers"],
                         seq_len=conf["seq_len"])
    m = client.cfg
    assert dict(vocab=m.vocab_size, d=m.d_model, layers=m.num_layers,
                hybrid_ids=tuple(i for i in m.hybrid.layer_ids
                                 if i < m.num_layers),
                shared_blocks=m.hybrid.num_shared_blocks, heads=m.num_heads,
                head_dim=m.resolved_head_dim(), ff=m.d_ff,
                rank=m.hybrid.adapter_rank, expand=m.ssm.expand,
                ssm_head_dim=m.ssm.head_dim, state=m.ssm.state_dim,
                groups=m.ssm.ngroups, conv=m.ssm.conv_width,
                chunk=m.ssm.chunk, rope_theta=m.rope_theta,
                eps=m.norm_eps) == ref.PUBLISHED
    assert ref.costs(dict(conf, div_tau=1, div_T=8))["transfer_width"] == \
        conf["model"]["per_device"]["values"]
