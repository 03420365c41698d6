"""What PR 38 added to the benchmark: thirteen per-layer metrics under the
first level of the compiled step's scope tree, read through
`harness/spec.read_layer_metrics` over a scope map made by hand from the
`op_name`s the four families' steps carry (forward, backward, recomputed);
the identities the new shares have to keep with the shares that were
there (and, since PR 40, `moe_rest_share.train` with them); a program
without the new scopes (the parent) reads none of them and nothing raises;
the new reader `perf_last_ratio`.

The six cells and the thirteen metrics are named here. Nothing counts or
indexes the manifest, which later PRs append to: `test_bench_manifest.py`
runs this module over a manifest with a seventh cell and one more metric."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import spec as spec_lib  # noqa: E402

MANIFEST = spec_lib.load_manifest(ROOT)
NEW = ["attention_core_share.train", "attention_proj_share.train",
       "conv_mix_share.train", "conv_proj_share.train",
       "kda_chain_share.train", "kda_prepare_share.train",
       "kda_inverse_share.train", "kda_kernel_share.train",
       "moe_shared_share.kimi.train", "moe_router_share.kimi.train",
       "moe_products_share.kimi.train", "setup_lower_s", "step_hbm_share"]
BERT = ["large-pretrain-128", "base-pretrain-128",
        "large-pretrain-512-packed"]
LFM2 = "lfm2-ep8-clm-8k-packed"
KIMI = "kimi-linear-ep32-clm-16k-packed"
SMALLTHINKER = "smallthinker-ep8-clm-16k-fullrow"
DECODERS = [LFM2, KIMI, SMALLTHINKER]
CELLS = BERT + DECODERS

STEP = "jit(train_step)/grad_accum/while/body/closed_call/"


def _bert(flash):
    layer = "bert/encoder/while/body/closed_call/"
    fwd = STEP + "jvp(BertForPreTraining)/" + layer + "layers/layer/"
    bwd = (STEP + "transpose(jvp(BertForPreTraining))/" + layer
           + "checkpoint/layers/layer/")
    again = (STEP + "transpose(jvp(BertForPreTraining))/" + layer
             + "checkpoint/rematted_computation/layers/layer/")
    core = ([("attn_core/flash_fwd/pallas_call", 4.0),
             ("attn_core/transpose", 0.5)] if flash else
            [("attn_core/bqhd,bkhd->bhqk/dot_general", 3.0),
             ("attn_core/exp", 1.5)])
    return [
        (fwd + "attention/attention/qkv/dot_general", 3.0, "proj"),
        (bwd + "attention/attention/qkv/dot_general", 5.0, "proj"),
        (again + "attention/attention/output/dot_general", 2.0, "proj"),
        *[(fwd + "attention/attention/" + op, t, "core") for op, t in core],
        (bwd + "attention/attention/attn_core/mul", 6.0, "core"),
        (again + "attention/attention/attn_core", 0.25, "core"),
        # the attention scope's own: the fused LayerNorm kernel, a slice
        (fwd + "attention/attention_layer_norm/add_dropout_layernorm_fwd/"
               "pallas_call", 1.0, "attention"),
        (fwd + "attention/attention/slice", 0.5, "attention"),
        (fwd + "mlp/intermediate/dot_general", 10.0, None),
        (STEP + "add", 1.0, None),
    ]


def _lfm2():
    fwd = STEP + "jvp(Lfm2MoeForCausalLM)/decoder/"
    bwd = (STEP + "transpose(jvp(Lfm2MoeForCausalLM))/decoder/"
           "jvp(Lfm2MoeForCausalLM)/decoder/checkpoint/")
    return [
        (fwd + "layer_0/conv/in_proj/dot_general", 3.0, "conv_proj"),
        (bwd + "layer_0/conv/out_proj/dot_general", 2.0, "conv_proj"),
        (fwd + "layer_0/conv/mix/split", 1.0, "conv_mix"),
        (bwd + "layer_0/conv/mix/mul", 4.0, "conv_mix"),
        (bwd + "rematted_computation/layer_2/conv/mix/jit(_pad)/pad", 0.5,
         "conv_mix"),
        (fwd + "layer_1/attention/attn_core/flash_fwd/pallas_call", 5.0,
         "core"),
        (bwd + "layer_1/attention/attn_core/flash_bwd_dq/pallas_call", 6.0,
         "core"),
        (fwd + "layer_1/attention/q_norm/rmsnorm/mul", 1.0, "attention"),
        (fwd + "layer_1/moe/moe/router/dot_general", 2.0, None),
        ("ragged-dot-none", 3.0, None),
    ]


def _kimi():
    fwd = STEP + "jvp(KimiLinearForCausalLM)/decoder/"
    bwd = (STEP + "transpose(jvp(KimiLinearForCausalLM))/decoder/"
           "jvp(KimiLinearForCausalLM)/decoder/checkpoint/")
    block = "layer_0/kda/kda/scan/while/body/closed_call/"
    return [
        (fwd + "layer_0/kda/dot_general", 2.0, "kda"),      # x @ (q | k | v)
        (bwd + "layer_0/kda/checkpoint/kda/conv/mul", 6.0, "chain"),
        (bwd + "layer_0/kda/checkpoint/rematted_computation/kda/gates/exp",
         1.0, "chain"),
        (fwd + "layer_0/kda/kda/out/out_proj/dot_general", 3.0, "chain"),
        (fwd + block + "kda/scan/prepare/mul", 4.0, "prepare"),
        (fwd + block + "kda/scan/prepare/kda/scan/prepare/inverse/"
               "dot_general", 2.0, "inverse"),
        (bwd + block + "kda/scan/prepare/transpose(jvp(kda/scan/prepare/"
               "inverse))/dot_general", 1.5, "inverse"),
        (bwd + block + "kda/scan/prepare/transpose(kda/scan/prepare)/"
               "jvp(kda/scan/prepare/inverse)/neg", 0.5, "inverse"),
        (fwd + block + "kda/scan/kda_fwd/pallas_call", 1.0, "kernel"),
        (bwd + block + "kda/scan/kda_bwd/pallas_call", 1.5, "kernel"),
        (fwd + "layer_0/kda/kda/scan/transpose", 1.0, "scan"),
        (fwd + "layer_3/attention/attn_core/mla_flash_fwd/pallas_call", 3.0,
         "core"),
        (fwd + "layer_3/attention/q_proj/dot_general", 2.0, "attention"),
        (fwd + "layer_1/moe/moe/router/dot_general", 1.0, "router"),
        (fwd + "layer_1/moe/moe/shared/w1/dot_general", 2.0, "shared"),
        (bwd + "layer_1/moe/moe/experts/mul", 0.5, "products"),
        ("ragged-dot-none", 1.5, "products"),
        (fwd + "layer_1/moe/moe/dispatch/gather", 1.0, "dispatch"),
        (bwd + "layer_1/moe/moe/combine/scatter-add", 1.0, "dispatch"),
        (fwd + "layer_1/moe/convert_element_type", 0.5, "moe"),
        (STEP + "add", 1.0, None),
    ]


def _smallthinker():
    fwd = STEP + "jvp(SmallThinkerForCausalLM)/decoder/checkpoint/"
    return [
        (fwd + "layer_0/attention/attention_full/attn_core/flash_fwd/"
               "pallas_call", 4.0, "core"),
        (fwd + "rematted_computation/layer_1/attention/attention_window/"
               "attn_core/flash_win_bwd_dq/pallas_call", 6.0, "core"),
        (fwd + "layer_1/attention/attention_window/dot_general", 3.0,
         "attention"),
        (fwd + "layer_1/moe/moe/router/dot_general", 2.0, None),
        ("ragged-dot-none", 5.0, None),
    ]


ROWS = {**{cell: _bert(cell == BERT[2]) for cell in BERT},
        LFM2: _lfm2(), KIMI: _kimi(), SMALLTHINKER: _smallthinker()}
# which rows a metric sums (a row's tag); every cell's last [perf] record
TAGS = {
    "attention_core_share.train": {"core"},
    "attention_proj_share.train": {"proj"},
    "conv_mix_share.train": {"conv_mix"},
    "conv_proj_share.train": {"conv_proj"},
    "kda_chain_share.train": {"chain"},
    "kda_prepare_share.train": {"prepare", "inverse"},
    "kda_inverse_share.train": {"inverse"},
    "kda_kernel_share.train": {"kernel"},
    "moe_shared_share.kimi.train": {"shared"},
    "moe_router_share.kimi.train": {"router"},
    "moe_products_share.kimi.train": {"products"},
}
PERF = {"setup_lower_s": 27.5, "step_peak_bytes": 15_610_000_000,
        "hbm_bytes_limit": 16_909_334_528}


def _ctx(cell, rows=None, perf=PERF):
    rows = ROWS[cell] if rows is None else rows
    by_scope = {path: t for path, t, _ in rows}
    assert len(by_scope) == len(rows)
    return {"trace": {"by_scope": by_scope,
                      "busy_s": sum(t for _, t, _ in rows) + 1.0},
            "record": {"window": {"perf": [{}, dict(perf)]}}}


def _read(cell, names, ctx):
    entries = [m for m in MANIFEST["per_layer"] if m["name"] in names]
    assert len(entries) == len(names)
    got = spec_lib.read_layer_metrics(dict(MANIFEST, per_layer=entries),
                                      cell, ctx, ROOT)
    return {name: m["value"] for name, m in got.items()}


def _share(cell, tags):
    rows = ROWS[cell]
    busy = sum(t for _, t, _ in rows) + 1.0
    return 100.0 * sum(t for _, t, tag in rows if tag in tags) / busy


def _asked(cell, names=NEW):
    """Which of `names` the manifest asks of the cell, with a list of cells
    on the metric or without one (`spec.metrics_of_cell`)."""
    return [m["name"] for m in spec_lib.metrics_of_cell(
        MANIFEST, cell, "per_layer") if m["name"] in names]


def test_the_thirteen_are_in_the_manifest_and_asked_of_their_cells():
    assert sorted(m["name"] for m in MANIFEST["per_layer"]
                  if m["name"] in NEW) == sorted(NEW)
    of = {name: [cell for cell in CELLS if name in _asked(cell)]
          for name in NEW}
    # every cell reports these three (since PR 40 their entries carry no
    # list of cells, so a cell that a later PR adds reports them too:
    # test_bench_manifest.py sees that of its seventh cell)
    for name in ("attention_core_share.train", "setup_lower_s",
                 "step_hbm_share"):
        assert of[name] == CELLS
    assert of["attention_proj_share.train"] == BERT
    assert {tuple(v) for k, v in of.items() if k.startswith("conv_")} \
        == {(LFM2,)}
    assert {tuple(v) for k, v in of.items()
            if k.startswith("kda_") or ".kimi." in k} == {(KIMI,)}
    readers = {spec_lib.load_layer_metric(name, ROOT)["reader"]
               for name in NEW}
    assert readers == {"scope_sum_share", "kernel_share", "perf_last_field",
                       "perf_last_ratio"}


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_reads_its_own_of_the_thirteen(cell):
    got = _read(cell, NEW, _ctx(cell))
    listed = _asked(cell)
    assert sorted(got) == sorted(listed)
    for name, tags in TAGS.items():
        if name in listed:
            assert got[name] == pytest.approx(_share(cell, tags)), name
    assert got["setup_lower_s"] == 27.5
    assert got["step_hbm_share"] == pytest.approx(
        100.0 * 15_610_000_000 / 16_909_334_528)
    assert got["step_hbm_share"] < 100


def test_the_new_shares_close_on_the_shares_that_were_there():
    """Tentpole (d): what the sums must do, on the hand-made map (the
    chip's residuals are PERF.md section 5's)."""
    old = ["kda_share.train", "kda_scan_share.train", "moe_share.kimi.train",
           "moe_dispatch_share.kimi.train", "attention_share.train"]
    k = _read(KIMI, NEW + old, _ctx(KIMI))
    # the q/k/v projection sits under `kda` and under no child
    assert (k["kda_chain_share.train"] + k["kda_scan_share.train"]
            + _share(KIMI, {"kda"})) == pytest.approx(k["kda_share.train"])
    assert (k["kda_prepare_share.train"] + k["kda_kernel_share.train"]
            + _share(KIMI, {"scan"})) == pytest.approx(
                k["kda_scan_share.train"])
    assert 0 < k["kda_inverse_share.train"] < k["kda_prepare_share.train"]
    assert (k["moe_router_share.kimi.train"]
            + k["moe_shared_share.kimi.train"]
            + k["moe_products_share.kimi.train"]
            + k["moe_dispatch_share.kimi.train"]
            + _share(KIMI, {"moe"})) == pytest.approx(
                k["moe_share.kimi.train"])
    lf = _read(LFM2, NEW + ["conv_share.train"], _ctx(LFM2))
    assert lf["conv_mix_share.train"] + lf["conv_proj_share.train"] == \
        pytest.approx(lf["conv_share.train"])
    for cell in CELLS:
        got = _read(cell, NEW + ["attention_share.train"], _ctx(cell))
        parts = got["attention_core_share.train"] + got.get(
            "attention_proj_share.train", 0.0)
        assert parts + _share(cell, {"attention"}) == pytest.approx(
            got["attention_share.train"]), cell


REST = "moe_rest_share.train"
MOE = {LFM2: "moe_share.train", KIMI: "moe_share.kimi.train",
       SMALLTHINKER: "moe_share.smallthinker.train"}
MOE_CHILDREN = ["moe_router_share.kimi.train", "moe_shared_share.kimi.train",
                "moe_products_share.kimi.train",
                "moe_dispatch_share.kimi.train"]


@pytest.mark.parametrize("cell", DECODERS)
def test_what_is_left_under_moe_closes_the_routed_layers_share(cell):
    """PR 40's `moe_rest_share.train` (reader `scope_under_share`: under
    `moe` and under none of its five children; the held experts' casts, the
    backward loop's `moe/accumulate`): asked of the three decoder cells; on
    the kimi map the children and it are `moe_share.kimi.train`, on the
    others the routed layers' share less the router and the grouped
    products; silent where nothing is left, never 0."""
    assert _asked(cell, [REST]) == [REST]
    assert not any(_asked(other, [REST]) for other in BERT)
    spec = spec_lib.load_layer_metric(REST, ROOT)
    assert spec["reader"] == "scope_under_share"
    assert spec["args"]["scope"] == "moe" and sorted(
        spec["args"]["outside"]) == ["moe/combine", "moe/dispatch",
                                     "moe/experts", "moe/router",
                                     "moe/shared"]
    rows = ROWS[cell] + [
        (STEP + "transpose(jvp(Decoder))/decoder/layer_1/moe/moe/accumulate/"
                "add", 0.25, "moe")]
    got = _read(cell, [REST, MOE[cell]] + MOE_CHILDREN, _ctx(cell, rows))
    busy = sum(t for _, t, _ in rows) + 1.0
    left = sum(t for _, t, tag in rows if tag == "moe")
    assert got[REST] == pytest.approx(100.0 * left / busy)
    if cell == KIMI:
        assert left == 0.75         # the cast the map had, and the add
        assert sum(got[name] for name in MOE_CHILDREN) + got[REST] == \
            pytest.approx(got[MOE[cell]])
    else:
        named = sum(t for path, t, _ in rows if "/moe/router/" in path
                    or path == "ragged-dot-none")
        assert got[MOE[cell]] - got[REST] == pytest.approx(
            100.0 * named / busy)
    # a step that leaves nothing under `moe` outside the five children
    bare = [row for row in rows if row[2] != "moe"]
    assert REST not in _read(cell, [REST, MOE[cell]], _ctx(cell, bare))


@pytest.mark.parametrize("cell", CELLS)
def test_a_program_without_the_new_scopes_reads_none_of_them(cell):
    """The parent's step: `attn_core`, `mix` and `prepare` are not in its
    op_names (the driver's traced runs lay this PR's benchmark files over
    the parent's checkout too)."""
    def parent(path):       # it opened `kda/scan` where `prepare` is now
        for part, was in (("/attn_core", ""), ("/mix", ""),
                          ("kda/scan/prepare/inverse", "kda/scan"),
                          ("kda/scan/prepare", "kda/scan")):
            path = path.replace(part, was)
        return path

    rows, seen = [], set()
    for path, t, tag in ROWS[cell]:
        if parent(path) not in seen:
            seen.add(parent(path))
            rows.append((parent(path), t, tag))
    perf = {k: v for k, v in PERF.items() if k != "setup_lower_s"}
    got = _read(cell, NEW + ["attention_share.train"],
                _ctx(cell, rows, perf))
    assert "attention_share.train" in got
    silent = {"attention_core_share.train", "conv_mix_share.train",
              "kda_prepare_share.train", "kda_inverse_share.train",
              "setup_lower_s"}
    assert not silent & set(got)
    # what the parent's program has already is read from it
    assert ("conv_proj_share.train" in got) == (cell == LFM2)
    assert ("kda_chain_share.train" in got) == (cell == KIMI)
    assert ("attention_proj_share.train" in got) == (cell in BERT)
    # no scopes at all: nothing of the device's, and nothing raises
    bare = {"trace": {"by_scope": {"": 3.0}, "busy_s": 3.0},
            "record": {"window": {"perf": []}}}
    assert _read(cell, NEW, bare) == {}


@pytest.mark.parametrize("records,want", [
    ([{"step_peak_bytes": 8_000, "hbm_bytes_limit": 16_000}], 50.0),
    ([{"step_peak_bytes": 1}, {"step_peak_bytes": 12_000,
                               "hbm_bytes_limit": 16_000}], 75.0),
    ([{"step_peak_bytes": 0, "hbm_bytes_limit": 16_000}], 0.0),
    ([{"hbm_bytes_limit": 16_000}], None),              # no peak stated
    ([{"step_peak_bytes": 8_000}], None),               # the CPU: no limit
    ([{"step_peak_bytes": 8_000, "hbm_bytes_limit": 0}], None),
    ([], None),
], ids=["ratio", "last-record", "zero-peak", "missing-field",
        "missing-limit", "zero-limit", "no-records"])
def test_perf_last_ratio(records, want):
    read = spec_lib.load_reader("perf_last_ratio", ROOT)
    args = spec_lib.load_layer_metric("step_hbm_share", ROOT)["args"]
    assert args == {"field": "step_peak_bytes", "over": "hbm_bytes_limit"}
    got = read({"record": {"window": {"perf": records}}}, **args)
    assert got == want
