"""Tracing from inside the program (ISSUE 27): the scope index of a
compiled step, fit's spans on the profiler's clock, and the compile
counters."""

import glob
import json
import os

import numpy as np
import pytest

from analytics_zoo_tpu.common import profiling, telemetry


@pytest.fixture(autouse=True)
def fresh_telemetry():
    telemetry.reset_for_tests()
    yield
    telemetry.reset_for_tests()


def _tiny_bert_estimator(clip: bool = False):
    """The benchmark's tiny BERT classifier behind ``Estimator.from_flax``
    and 32 rows to feed it."""
    from analytics_zoo_tpu.learn.estimator import Estimator
    from benchmarks.harness import tiny
    from benchmarks.harness.manifest import ROOT
    from benchmarks.models import bert as model_lib

    cfg = json.loads((ROOT / "benchmarks" / "configs"
                      / "bert-base.json").read_text())
    cfg.update(tiny.TINY_CONFIG["bert"], compute_dtype="float32")
    x, y = model_lib.make_inputs(cfg, {"seq_len": 16},
                                 np.random.default_rng(0), 32)
    est = Estimator.from_flax(model=model_lib.build_module(cfg),
                              loss=model_lib.LOSS, optimizer="adam",
                              sample_input=x[:2])
    if clip:
        est.set_l2_norm_gradient_clipping(1.0)
    return est, x, y


def _fit_and_wait(est, x, y, **fit_args):
    est.fit((x, y), epochs=1, batch_size=8, **fit_args)
    est._precompile_thread.join(timeout=300)
    assert not est._precompile_thread.is_alive()


# ------------------------------------------------------- the scope index

@pytest.fixture
def step_index(orca_ctx):
    est, x, y = _tiny_bert_estimator(clip=True)
    _fit_and_wait(est, x, y)
    index = profiling.scope_index("estimator_train_step")
    assert index
    return index


def _phases(index, pattern):
    import re
    rx = re.compile(pattern)
    return {e["phase"] for e in index.values()
            if e["scope"] and rx.search(e["scope"])}


def test_scope_index_names_the_parts_of_the_step(step_index):
    """Flax names the model's parts, fit's step names the rest; forward
    and backward ops of one module share its scope and differ in phase."""
    assert {"forward", "backward"} <= _phases(
        step_index, r"block_\d+/attention(/|$)")
    assert {"forward", "backward"} <= _phases(
        step_index, r"block_\d+/intermediate$")
    assert {"forward", "backward"} <= _phases(
        step_index, r"block_\d+/output$")
    assert _phases(step_index, r"^optimizer(/|$)") == {"optimizer"}
    assert {"forward", "backward"} <= _phases(step_index, r"^loss(/|$)")
    assert _phases(step_index, r"^clip(/|$)") == {"other"}
    assert _phases(step_index, r"^metrics$") == {"other"}
    # no wrapper of a transform is left in a scope, no primitive's name;
    # no entry names a kernel (``kernel``, ``tiles``): BERT's step on the
    # CPU launches none
    for entry in step_index.values():
        assert set(entry) == {"scope", "phase", "scopes", "opcode"}
        for scope in entry["scopes"]:
            assert "jit(" not in scope and "jvp(" not in scope
            assert not scope.endswith("dot_general")
        assert entry["phase"] in ("forward", "backward", "optimizer",
                                  "other")
    assert profiling.scope_index("no_such_executable") is None


def test_scope_index_outlives_the_estimator_and_serializes(step_index):
    import gc
    gc.collect()            # the estimator of the fixture is gone
    again = profiling.scope_index("estimator_train_step")
    assert again is step_index          # parsed once
    assert json.loads(json.dumps(again)) == again


PLANTED = """\
HloModule jit_step_fn, is_scheduled=true

%fused_computation.1 (p0: f32[8,8], p1: f32[8,8], p2: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %p1 = f32[8,8]{1,0} parameter(1)
  %p2 = f32[8,8]{1,0:T(8,128)S(1)} parameter(2)
  %dot.7 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %p0, f32[8,8]{1,0} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step_fn)/transpose(jvp(Classifier))/bert/block_0/output/dot_general" stack_frame_id=7}
  ROOT %add.9 = f32[8,8]{1,0} add(f32[8,8]{1,0} %dot.7, f32[8,8]{1,0:T(8,128)S(1)} %p2), metadata={op_name="jit(step_fn)/optimizer/add"}
}

%fused_computation.2 (q0: f32[8,8]) -> f32[8,8] {
  %q0 = f32[8,8]{1,0} parameter(0)
  ROOT %exp.3 = f32[8,8]{1,0} exponential(f32[8,8]{1,0} %q0), metadata={op_name="jit(step_fn)/jvp(Classifier)/bert/block_0/attention/exp"}
}

%body.4 (carry: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %carry = (s32[], f32[8,8]{1,0}) parameter(0)
  %gte.1 = f32[8,8]{1,0} get-tuple-element((s32[], f32[8,8]{1,0}) %carry), index=1
  %negate.5 = f32[8,8]{1,0} negate(f32[8,8]{1,0} %gte.1), metadata={op_name="jit(step_fn)/jvp(Classifier)/bert/Dropout_0/while/body/neg"}
  ROOT %tuple.6 = (s32[], f32[8,8]{1,0}) tuple(s32[] %c, f32[8,8]{1,0} %negate.5)
}

ENTRY %main.10 (a: f32[8,8], b: f32[8,8], c: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0), metadata={op_name="state[\\'params\\'][\\'w\\']"}
  %b = f32[8,8]{1,0} parameter(1)
  %c = f32[8,8]{1,0} parameter(2)
  %fusion.12 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %a, f32[8,8]{1,0} %b, f32[8,8]{1,0} %c), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step_fn)/optimizer/add"}
  %exp_fusion = (f32[8,8]{1,0:T(8,128)S(1)}, f32[8,8]{1,0}) fusion(f32[8,8]{1,0} %fusion.12), kind=kLoop, calls=%fused_computation.2
  %copy-start.3 = (f32[8,8]{1,0}, f32[8,8]{1,0}, u32[]) copy-start(f32[8,8]{1,0} %b)
  %copy-done.3 = f32[8,8]{1,0} copy-done((f32[8,8]{1,0}, f32[8,8]{1,0}, u32[]) %copy-start.3)
  %bitcast.4 = f32[64]{0} bitcast(f32[8,8]{1,0} %copy-done.3)
  %fusion.13 = f32[64]{0} fusion(f32[64]{0} %bitcast.4), kind=kLoop, calls=%fused_computation.2
  %copy-start.5 = (f32[8,8]{1,0}, f32[8,8]{1,0}, u32[]) copy-start(f32[8,8]{1,0} %fusion.12)
  %copy-done.5 = f32[8,8]{1,0} copy-done((f32[8,8]{1,0}, f32[8,8]{1,0}, u32[]) %copy-start.5)
  %iota.6 = s32[8]{0} iota(), iota_dimension=0
  %while.8 = (s32[], f32[8,8]{1,0}) while((s32[], f32[8,8]{1,0}) %t), condition=%cond.5, body=%body.4
  ROOT %neg.2 = f32[8,8]{1,0} negate(f32[8,8]{1,0} %exp_fusion), metadata={op_name="jit(step_fn)/jit(_where)/neg"}
}
"""


def test_a_planted_two_scope_fusion_counts_with_its_product_and_is_mixed():
    index = profiling.parse_scope_index(PLANTED)
    # the weight-gradient product fused with the optimizer's update
    fused = index["fusion.12"]
    assert fused["scope"] == "Classifier/bert/block_0/output"
    assert fused["phase"] == "backward"
    assert fused["scopes"] == ["Classifier/bert/block_0/output",
                               "optimizer"]
    # no product inside: the fused computation's root names it, though
    # the fusion instruction itself carries no metadata and a tuple shape
    assert index["exp_fusion"] == {
        "scope": "Classifier/bert/block_0/attention", "phase": "forward",
        "scopes": ["Classifier/bert/block_0/attention"],
        "opcode": "fusion"}
    # a weight brought in ahead of its use, and the wait for it: nameless,
    # so they count with the nearest named consumer of the result ...
    for name in ("copy-start.3", "copy-done.3"):
        assert index[name]["scope"] == "Classifier/bert/block_0/attention"
        assert index[name]["phase"] == "forward"
        assert index[name]["scopes"] == []
    # ... a result copied out of the step, with what produced it ...
    assert index["copy-done.5"]["scope"] == "Classifier/bert/block_0/output"
    assert index["copy-done.5"]["phase"] == "backward"
    # ... and what has neither is known, without scope
    assert index["iota.6"]["scope"] is None
    assert index["iota.6"]["scopes"] == []
    # directly under jit(step_fn), inside another jitted helper: no scope
    assert index["neg.2"]["scope"] == "" and index["neg.2"]["phase"] == "other"
    # a container's body runs as ops of its own and is indexed ...
    assert index["while.8"]["opcode"] == "while"
    assert index["negate.5"]["scope"] \
        == "Classifier/bert/Dropout_0/while/body"
    # ... what runs inside a fusion is not, nor what takes no device time
    assert not {"dot.7", "add.9", "exp.3", "a", "gte.1", "tuple.6",
                "bitcast.4"} & set(index)


def test_profile_window_leaves_the_scope_index_beside_its_trace(
        orca_ctx, tmp_path):
    est, x, y = _tiny_bert_estimator()
    est.set_tensorboard(str(tmp_path), "run")
    _fit_and_wait(est, x, y)            # builds the step ahead of time
    est.fit((x, y), epochs=1, batch_size=8, profile_steps=(1, 3))
    runs = glob.glob(os.path.join(est._tb_dirs[0], "plugins", "profile",
                                  "*"))
    assert len(runs) == 1
    assert glob.glob(os.path.join(runs[0], "*.xplane.pb"))
    with open(os.path.join(runs[0], "scope_index.json")) as fh:
        written = json.load(fh)
    assert list(written) == ["estimator_train_step", "counts"]
    assert written["estimator_train_step"] \
        == profiling.scope_index("estimator_train_step")
    counts = written["counts"]["estimator_train_step"]
    assert counts == profiling.step_counts("estimator_train_step")
    # the tiny BERT has 2 blocks: 2 exact gelus, 1 + 2 x 2 dropout sites
    assert counts["held_values"] == 7


# ----------------------------------------- held values and evaluations

PARENT_FUSIONS = os.path.join(os.path.dirname(__file__), "fixtures",
                              "hlo_parent_s512_output_fusions.txt")


def test_evaluations_counted_in_three_fusions_of_the_unheld_step():
    """The three fused computations around block 5's ``output`` product —
    forward, input gradient, weight gradient + Adam — of the s512 step as
    the v5e compiled it before the residuals were held (PR 27's kept HLO,
    cut with the computations they call): each re-derives the erfc and
    the dropout mask inside itself."""
    with open(PARENT_FUSIONS) as fh:
        text = fh.read()
    assert text.count(" convolution(") == 3
    assert profiling.count_elementwise_evals(text) == {"erfc": 3, "mask": 3}
    # nothing else of the text reads as either
    assert profiling.count_elementwise_evals(
        text.replace("/erfc\"", "/erf_inv\"")
            .replace("jit(_bernoulli)/lt\"", "jit(_bernoulli)/le\"")) \
        == {"erfc": 0, "mask": 0}


class _CannedExe:
    """What ``note_executable`` reads of a compiled executable."""

    def __init__(self, text):
        self.text = text

    def as_text(self):
        return self.text

    def cost_analysis(self):
        return {"flops": 2.0}


@pytest.mark.parametrize("n_lowered,want", [(0, None), (1, 3)])
def test_note_executable_counts_and_publishes(n_lowered, want):
    with open(PARENT_FUSIONS) as fh:
        text = fh.read()

    class Lowered:
        def as_text(self):
            return ("%0 = stablehlo.optimization_barrier %a : tensor<4xi1>\n"
                    * 3)

    lowered = Lowered() if n_lowered else None
    assert profiling.note_executable("canned", _CannedExe(text),
                                     lowered=lowered) == 2.0
    counts = profiling.step_counts("canned")
    assert counts.get("held_values") == want
    assert (counts["erfc"], counts["mask"]) == (3, 3)
    snap = telemetry.snapshot()
    assert snap["zoo_step_elementwise_evals"] == {
        "executable=canned,kind=erfc": 3, "executable=canned,kind=mask": 3}
    assert snap.get("zoo_step_held_values") == (
        {"executable=canned": 3} if want else None)
    assert profiling.step_counts("no_such_executable") is None


REMAT_LAYER = os.path.join(os.path.dirname(__file__), "fixtures",
                           "hlo_v5e_remat_attention_layer_entry.txt")


@pytest.mark.parametrize("fixture,want", [
    (REMAT_LAYER, {"flash_fwd": 2, "flash_bwd_dq": 1, "flash_bwd_dkv": 1,
                   "norm_rotary_fwd": 0, "norm_rotary_bwd": 0,
                   "moe_pack_rows": 0, "moe_sum_rows": 0}),
    (PARENT_FUSIONS, {"flash_fwd": 0, "flash_bwd_dq": 0,
                      "flash_bwd_dkv": 0, "norm_rotary_fwd": 0,
                      "norm_rotary_bwd": 0, "moe_pack_rows": 0,
                      "moe_sum_rows": 0})],
    ids=["a_layer_that_keeps_products_alone", "no_kernel"])
def test_kernel_calls_counted_and_published(fixture, want):
    """The entry computation of ``tanh(x @ w) -> flash_attention -> @ w``
    under ``jax.checkpoint`` keeping products alone, as the v5e compiler
    left it: the forward kernel once for the forward pass and once more
    for the backward's ``out`` and ``lse``. BERT's fusions call no
    kernel, and publish zeros as they do for the evaluations."""
    with open(fixture) as fh:
        text = fh.read()
    assert profiling.count_kernel_calls(text) == want
    profiling.note_executable("canned", _CannedExe(text))
    counts = profiling.step_counts("canned")
    assert {k: counts[k] for k in want} == want
    assert telemetry.snapshot()["zoo_step_kernel_calls"] == {
        f"executable=canned,kernel={k}": n for k, n in want.items()}
    # a kernel is told by its function's name inside the call's body and
    # by nothing else of the line
    assert sum(profiling.count_kernel_calls(
        text.replace('"body":"', '"body":"A')).values()) == 0


LIVE_TILES_LAYER = os.path.join(
    os.path.dirname(__file__), "fixtures",
    "hlo_v5e_live_tiles_attention_layer_entry.txt")


@pytest.mark.parametrize("fixture,want", [
    (LIVE_TILES_LAYER, {f"{kernel}/{kind}": n
                        for kernel in profiling.FLASH_KERNELS
                        for kind, n in (("interior", 2), ("diagonal", 4),
                                        ("dead", 0))}),
    (REMAT_LAYER, {}), (PARENT_FUSIONS, {})],
    ids=["launches_that_list_their_tiles", "launches_that_list_nothing",
         "no_kernel"])
def test_flash_grid_steps_counted_and_published(fixture, want):
    """The same layer under the decoder's policy at ``[1, 1024, 2, 64]``
    and 512 x 512 blocks, as the v5e compiler left it: each launch says in
    its call's ``kernel_metadata`` how many grid steps of each kind it
    listed (two heads of one interior and two diagonal tiles, no dead
    step). A launch that says nothing (the kernels before they listed
    their tiles) and a program without the kernels give no series."""
    with open(fixture) as fh:
        text = fh.read()
    assert profiling.count_flash_grid_steps(text) == want
    profiling.note_executable("canned", _CannedExe(text))
    counts = profiling.step_counts("canned")
    assert {k: n for k, n in counts.items() if "/" in k} == want
    assert telemetry.snapshot().get("zoo_flash_grid_steps") == ({
        "executable=canned,kernel={},kind={}".format(*k.split("/")): n
        for k, n in want.items()} or None)


def _with_layouts(text, *said):
    """``text`` with ``layout`` and ``kv`` keys added to its launches'
    ``kernel_metadata`` as the compiler prints them (sorted, one a line),
    the n-th custom call of a kernel saying ``said[n]``."""
    out, n = [], 0
    for chunk in text.split('"interior":"2"\n'):
        out.append(chunk)
        if 'custom_call_target="tpu_custom_call"' in chunk:
            layout, kv = said[n]
            n += 1
            out.append(f'"interior":"2",\n"kv":"{kv}",\n"layout":"{layout}"\n')
        else:
            out.append('"interior":"2"\n')
    return "".join(out[:-1])


@pytest.mark.parametrize("said,want", [
    ([("rows", "grouped")] * 3,
     {"flash_fwd@rows,grouped": 1, "flash_bwd_dq@rows,grouped": 1,
      "flash_bwd_dkv@rows,grouped": 1}),
    ([("heads", "grouped"), ("heads", "own"), ("rows", "own")],
     {"flash_fwd@heads,grouped": 1, "flash_bwd_dq@heads,own": 1,
      "flash_bwd_dkv@rows,own": 1}),
    (None, {})],
    ids=["the_block_diffusion_cells", "each_launch_its_own",
         "launches_that_say_neither"])
def test_flash_layouts_counted_and_published(said, want):
    """The launches of the live-tiles layer with the two keys a launch
    writes since it finds a head's blocks by its index maps: counted by
    kernel, layout and grouping beside the grid steps, which read what
    they read without the keys. A launch from before the keys gives no
    series."""
    with open(LIVE_TILES_LAYER) as fh:
        text = fh.read()
    steps = profiling.count_flash_grid_steps(text)
    if said is not None:
        text = _with_layouts(text, *said)
        assert text.count('"layout":"') >= 3
    assert profiling.count_flash_layouts(text) == want
    assert profiling.count_flash_grid_steps(text) == steps
    assert profiling.count_kernel_calls(text) == {
        "flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1,
        "norm_rotary_fwd": 0, "norm_rotary_bwd": 0,
        "moe_pack_rows": 0, "moe_sum_rows": 0}
    profiling.note_executable("canned", _CannedExe(text))
    counts = profiling.step_counts("canned")
    assert {k: n for k, n in counts.items() if "@" in k} == want
    assert {k: n for k, n in counts.items() if "/" in k} == steps
    assert telemetry.snapshot().get("zoo_flash_launches") == ({
        "executable=canned,kernel={},layout={},kv={}".format(
            k.split("@")[0], *k.split("@")[1].split(",")): n
        for k, n in want.items()} or None)


def test_an_instruction_printed_over_several_lines_keeps_its_scope():
    """XLA prints a call's non-empty ``kernel_metadata`` one key a line;
    the three kernels are still counted once each and still belong to the
    attention layer's scope, forward and backward."""
    with open(LIVE_TILES_LAYER) as fh:
        text = fh.read()
    assert 'kernel_metadata={\n"dead":"0"' in text
    assert profiling.count_kernel_calls(text) == {
        "flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1,
        "norm_rotary_fwd": 0, "norm_rotary_bwd": 0,
        "moe_pack_rows": 0, "moe_sum_rows": 0}
    kernels = {name: (e["scope"], e["phase"])
               for name, e in profiling.parse_scope_index(text).items()
               if e["opcode"] == "custom-call"}
    assert sorted(kernels.values()) == [
        ("block_1/attention", "forward"),
        ("checkpoint/block_1/attention", "backward"),
        ("checkpoint/block_1/attention", "backward")]


def test_the_counted_kernel_functions_are_the_packages_kernels():
    from analytics_zoo_tpu.ops import flash_attention, moe_combine, norm_rotary
    for function in profiling.FLASH_KERNELS.values():
        assert callable(getattr(flash_attention, function.decode()))
    others = {"norm_rotary_fwd": norm_rotary, "norm_rotary_bwd": norm_rotary,
              "moe_pack_rows": moe_combine, "moe_sum_rows": moe_combine}
    assert set(profiling.KERNEL_FUNCTIONS) - set(profiling.FLASH_KERNELS) \
        == set(others)
    for kernel, module in others.items():
        assert callable(getattr(
            module, profiling.KERNEL_FUNCTIONS[kernel].decode()))
    assert profiling.TILE_KINDS == flash_attention.TILE_KINDS


def _kernel_call(name, body: bytes, metadata=""):
    """One custom call of the TPU compiler's optimized HLO whose kernel
    body is ``body`` (base64 as the compiler writes it)."""
    import base64
    said = f", frontend_attributes={{kernel_metadata={{{metadata}}}}}" \
        if metadata else ""
    return (f"  %{name} = bf16[1,256,512]{{2,1,0}} custom-call(bf16[1,256,"
            f"512]{{2,1,0}} %p), custom_call_target=\"tpu_custom_call\"{said},"
            ' metadata={op_name="jit(step)/block_1/attention/pallas_call"}, '
            'backend_config={"custom_call_config":{"body":"'
            + base64.b64encode(body).decode() + '"}}\n')


def _norm_rotary_step(fwd: bytes, bwd: bytes) -> str:
    """A step's entry computation with four launches of a kernel whose
    body names ``fwd``, two of ``bwd`` and one flash forward launch."""
    return ("ENTRY %main (p: bf16[1,256,512]) -> bf16[1,256,512] {\n"
            "  %p = bf16[1,256,512]{2,1,0} parameter(0)\n"
            + "".join(_kernel_call(f"attention.{i}", b"MLIR\x00" + fwd)
                      for i in range(4))
            + "".join(_kernel_call(f"attention.{i}", b"MLIR\x00" + bwd)
                      for i in (4, 5))
            + _kernel_call("attention.6", b"MLIR\x00_flash_fwd_kernel\x00",
                           '\n"dead":"0",\n"diagonal":"4",\n"interior":"2",'
                           '\n"kv":"grouped",\n"layout":"rows"\n')
            + "}\n")


def test_norm_rotary_launches_are_counted_beside_the_flash_kernels():
    """A step whose attention layer runs the q/k norm-and-rotary kernels:
    each launch is told by its kernel function's name inside the body and
    counted under its own label; it lists no tiles and says no layout, so
    the flash counters read the flash launches alone."""
    text = _norm_rotary_step(b"_norm_rotary_fwd_kernel\x00",
                             b"_norm_rotary_bwd_kernel\x00")
    want = {"flash_fwd": 1, "flash_bwd_dq": 0, "flash_bwd_dkv": 0,
            "norm_rotary_fwd": 4, "norm_rotary_bwd": 2,
            "moe_pack_rows": 0, "moe_sum_rows": 0}
    assert profiling.count_kernel_calls(text) == want
    assert profiling.count_flash_grid_steps(text) == {
        "flash_fwd/interior": 2, "flash_fwd/diagonal": 4, "flash_fwd/dead": 0}
    assert profiling.count_flash_layouts(text) == {
        "flash_fwd@rows,grouped": 1}
    profiling.note_executable("canned", _CannedExe(text))
    assert telemetry.snapshot()["zoo_step_kernel_calls"] == {
        f"executable=canned,kernel={k}": n for k, n in want.items()}
    # the function's name, not the launch's place or scope, tells them
    assert profiling.count_kernel_calls(_norm_rotary_step(
        b"_norm_rotary_fwd\x00", b"_rotary_bwd_kernel\x00")) == dict(
            want, norm_rotary_fwd=0, norm_rotary_bwd=0)


# ------------------------------------------- each launch in the index

EXPERT_LAYER = os.path.join(os.path.dirname(__file__), "fixtures",
                            "hlo_v5e_expert_layer_entry.txt")


@pytest.mark.parametrize("fixture,tiles", [
    (LIVE_TILES_LAYER, 6), (REMAT_LAYER, None)],
    ids=["launches_that_list_their_tiles", "launches_that_list_nothing"])
def test_each_flash_launch_names_its_kernel_in_the_index(fixture, tiles):
    """The attention layer's entry computation as the v5e compiler left
    it: the entry of each flash custom call says which kernel it launches
    and, where the launch listed its grid steps, how many (two heads of
    one interior and two diagonal tiles); no other entry names a kernel.
    What the counters read is the same from the one list of launches as
    from the text."""
    with open(fixture) as fh:
        text = fh.read()
    launches = profiling._kernel_launches(text)
    index = profiling.parse_scope_index(text)
    assert profiling.parse_scope_index(text, launches) == index
    named = {name: e for name, e in index.items() if "kernel" in e}
    assert {name: e["kernel"] for name, e in named.items()} \
        == {name: kernel for name, kernel, _ in launches}
    assert {e["kernel"] for e in named.values()} == set(
        profiling.FLASH_KERNELS)
    for entry in named.values():
        assert entry["opcode"] == "custom-call"
        assert entry["kernel"] in profiling.FLASH_KERNELS
        assert entry.get("tiles") == tiles
    for entry in index.values():
        if "kernel" not in entry:
            assert set(entry) == {"scope", "phase", "scopes", "opcode"}
    for count in (profiling.count_kernel_calls,
                  profiling.count_flash_grid_steps,
                  profiling.count_flash_layouts):
        assert count(launches) == count(text)


def test_the_expert_layers_grouped_products_are_named_ragged_dot():
    """The entry computation of one expert layer's gradient as the v5e
    compiler left it: the grouped products are the compiler's own custom
    calls, ``ragged-dot-none``, each named ``ragged_dot`` in the index
    under the layer's scope; ``ragged-dot-metadata`` (where the groups
    start) and every other instruction name no kernel. No pallas kernel of
    the package's is counted."""
    with open(EXPERT_LAYER) as fh:
        text = fh.read()
    index = profiling.parse_scope_index(text)
    products = {name for name, e in index.items()
                if e.get("kernel") == profiling.RAGGED_DOT}
    assert products and all(name.startswith("ragged-dot-none")
                            for name in products)
    assert products == {name for name, e in index.items()
                        if name.startswith("ragged-dot-none")}
    assert any(name.startswith("ragged-dot-metadata") and "kernel" not in e
               for name, e in index.items())
    import re
    for name in products:
        assert index[name]["opcode"] == "custom-call"
        assert re.search(r"block_\d+/moe(/|$)", index[name]["scope"])
        assert "tiles" not in index[name]
    assert set(profiling.count_kernel_calls(text).values()) == {0}
    assert profiling.count_flash_grid_steps(text) == {}
    assert profiling.count_flash_score_pairs(text) == {}


def test_note_executable_finds_the_launches_once(monkeypatch):
    """One pass over the launches at compile time: the counters read its
    list, and the scope index, parsed on first request, reads it again
    without a pass of its own."""
    with open(LIVE_TILES_LAYER) as fh:
        text = fh.read()
    calls = []
    found = profiling._kernel_launches

    def counted(hlo_text):
        calls.append(len(hlo_text))
        return found(hlo_text)

    monkeypatch.setattr(profiling, "_kernel_launches", counted)
    profiling.note_executable("canned", _CannedExe(text))
    assert len(calls) == 1
    index = profiling.scope_index("canned")
    assert len(calls) == 1
    assert index == profiling.parse_scope_index(text, found(text))
    assert sum("kernel" in e for e in index.values()) == 3


def _with_pairs(text, pairs, allowed):
    """``text`` with ``pairs`` and ``allowed`` among each launch's
    ``kernel_metadata``, in the compiler's order (sorted, one a line)."""
    return text.replace('"interior":"2"\n',
                        f'"interior":"2",\n"pairs":"{pairs}"\n').replace(
        '"diagonal":"4",\n', f'"allowed":"{allowed}",\n"diagonal":"4",\n')


def test_score_pairs_counted_and_published():
    """Each launch of the live-tiles layer with the two keys a launch
    writes since it counts its score pairs (two heads of three 512 x 512
    tiles; the causal mask allows 1,024 x 1,025 / 2 pairs a head): summed
    by kernel and published as ``zoo_flash_score_pairs``, computed and
    allowed, beside the grid steps, which read what they read."""
    with open(LIVE_TILES_LAYER) as fh:
        text = fh.read()
    steps = profiling.count_flash_grid_steps(text)
    text = _with_pairs(text, 2 * 3 * 512 * 512, 1024 * 1025)
    want = {f"{kernel}/{key}": n for kernel in profiling.FLASH_KERNELS
            for key, n in (("pairs", 1572864), ("allowed", 1049600))}
    assert profiling.count_flash_score_pairs(text) == want
    assert profiling.count_flash_grid_steps(text) == steps
    profiling.note_executable("canned", _CannedExe(text))
    counts = profiling.step_counts("canned")
    assert {k: counts[k] for k in want} == want
    assert {k: n for k, n in counts.items()
            if k.endswith(("/interior", "/diagonal", "/dead"))} == steps
    snap = telemetry.snapshot()
    assert snap["zoo_flash_score_pairs"] == {
        f"executable=canned,kernel={kernel},kind={kind}": n
        for kernel in profiling.FLASH_KERNELS
        for kind, n in (("computed", 1572864), ("allowed", 1049600))}
    assert set(snap["zoo_flash_grid_steps"]) == {
        f"executable=canned,kernel={k.split('/')[0]},kind={k.split('/')[1]}"
        for k in steps}
    # a launch from before the keys: no series
    telemetry.reset_for_tests()
    with open(LIVE_TILES_LAYER) as fh:
        profiling.note_executable("canned", _CannedExe(fh.read()))
    assert "zoo_flash_score_pairs" not in telemetry.snapshot()


#: a head's computed pairs at the two decoder cells' 1,024 x 1,024 blocks,
#: by the side of a strip: whole tiles (what the launches computed before
#: a diagonal tile computed its live strips alone), 128 and 256
DECODER_CELLS_COMPUTED = {
    "causal": {None: 37748736, 128: 34078720, 256: 34603008},
    "block_diffusion": {None: 83886080, 128: 69206016, 256: 71303168}}


@pytest.mark.parametrize("sq,heads,mask,allowed,share", [
    (8192, 64, "causal", 33558528, {128: 98.47, 256: 96.98}),
    (16384, 32, "block_diffusion", 67141632, {128: 97.02, 256: 94.16})],
    ids=["the_causal_cells_head", "the_block_diffusion_cells_head"])
def test_score_pairs_at_the_decoder_cells_shapes(sq, heads, mask, allowed,
                                                 share):
    """What a launch writes, counted in numpy with no compile, at the two
    decoder cells' 1,024 x 1,024 blocks: a causal head of 8,192 positions
    takes 36 tiles and wants 8,192 x 8,193 / 2 of their pairs; a head of
    the block-diffusion cell's 16,384 rows takes 80 tiles and wants 2 x
    33,570,816. A diagonal tile computes its live strips alone, so the
    computed pairs are the whole tiles' (88.9% and 80.04% wanted) less the
    dead strips: 96.98% and 94.16% wanted at 256-wide strips (98.47% and
    97.02% at 128). Every kind
    of launch computes the same pairs: the key-major table of a group of 8
    or 4 heads counts the group's heads."""
    from analytics_zoo_tpu.ops import flash_attention as fa
    m = fa.CausalMask(0) if mask == "causal" \
        else fa.BlockDiffusionMask(sq // 2, 4)
    n = sq // 1024
    computed = DECODER_CELLS_COMPUTED[mask]
    assert 100 * allowed / computed[None] == pytest.approx(
        88.9 if mask == "causal" else 80.04, abs=0.01)
    want = (computed[fa.STRIP], allowed)
    assert fa.tile_pairs(n, n, 1024, 1024, m, None) == want
    for groups in (1, 4, 8):
        assert fa.tile_pairs(n, n, 1024, 1024, m, None, True, groups) \
            == (want[0] * groups, want[1] * groups)
    assert 100 * want[1] / want[0] == pytest.approx(share[fa.STRIP],
                                                    abs=0.01)


@pytest.mark.parametrize("tile,mask", [
    ("noisy_diagonal", "block_diffusion"),
    ("clean_diagonal", "block_diffusion"),
    ("noisy_over_clean", "block_diffusion"),
    ("causal_diagonal", "causal")])
def test_strip_patterns_of_the_decoder_cells_diagonal_tiles(tile, mask):
    """Pure numpy, at the decoder cells' 1,024 x 1,024 blocks. The three
    kinds of diagonal tile of the block-diffusion cell and the causal
    cell's: per query strip the live key strips, and per key strip (the
    ``dk/dv`` launch's key-major table) the live query strips — a noisy
    strip sees its own strip alone; a clean strip, and a noisy one over
    the clean copy, the strips up to its own — each range crossing the
    mask's edge. Every diagonal tile of the cell is such a tile; the
    launch's table is ``tile_table``'s row for row, in its order, with
    the diagonal tiles coded by their pattern."""
    from analytics_zoo_tpu.ops import flash_attention as fa
    n = 1024 // fa.STRIP
    if mask == "causal":
        m, tiles, qi, ki = fa.CausalMask(0), 8, 3, 3
    else:
        m, tiles = fa.BlockDiffusionMask(8192, 4), 16
        qi, ki = {"noisy_diagonal": (2, 2), "clean_diagonal": (10, 10),
                  "noisy_over_clean": (2, 10)}[tile]
    if tile == "noisy_diagonal":
        by_query = by_key = [(r, r + 1, True) for r in range(n)]
    else:
        by_query = [(0, r + 1, True) for r in range(n)]
        by_key = [(c, n, True) for c in range(n)]
    assert fa.strips(qi, ki, 1024, 1024, m, None) == tuple(by_query)
    assert fa.strips(qi, ki, 1024, 1024, m, None, True) == tuple(by_key)
    assert fa._segments(tuple(by_query)) == tuple(
        (r * fa.STRIP, (r + 1) * fa.STRIP, lo * fa.STRIP, hi * fa.STRIP,
         True) for r, (lo, hi, _) in enumerate(by_query))
    for key_major, groups in ((False, 1), (True, 1), (True, 4)):
        table = fa.tile_table(tiles, tiles, 1024, 1024, m, None, key_major,
                              groups)
        launch, bodies = fa.launch_table(tiles, tiles, 1024, 1024, m, None,
                                         key_major, groups)
        assert launch.shape == table.shape
        assert (np.delete(launch, 2, 1) == np.delete(table, 2, 1)).all()
        diagonal = table[:, 2] == fa.DIAGONAL
        assert (launch[~diagonal, 2] == table[~diagonal, 2]).all()
        assert (launch[diagonal, 2] >= fa.STRIPS).all()
        want = by_key if key_major else by_query
        codes = dict(bodies)
        code = launch[(launch[:, 0] == qi) & (launch[:, 1] == ki), 2][0]
        assert codes[int(code)] == fa._segments(tuple(want))
        assert sorted(codes) == [fa.INTERIOR] + list(range(
            fa.STRIPS, fa.STRIPS + (1 if mask == "causal" else 2)))


@pytest.mark.parametrize("sq,sk,bq,bk,mask", [
    (200, 200, 128, 128, "causal"), (256, 300, 128, 128, "causal"),
    (1024, 1500, 512, 512, None), (1024, 1500, 512, 512, "causal"),
    (256, 256, 64, 128, "block_diffusion"),
    (384, 384, 128, 128, "block_diffusion_clean")],
    ids=["ragged_causal", "more_keys_than_queries", "padded_key_tail",
         "padded_key_tail_causal", "block_diffusion", "clean_half_alone"])
def test_allowed_pairs_match_a_brute_force_count(sq, sk, bq, bk, mask):
    """The allowed pairs of every live tile, summed over the table, equal
    the pairs of the padded score matrix that neither the mask nor the
    padded key tail excludes, counted whole in numpy; the computed pairs
    are, for each live tile, its live strips where some of its strips
    allow no pair and the live ones of each row lie side by side, and the
    whole tile otherwise — told from the same brute-force matrix."""
    from analytics_zoo_tpu.ops import flash_attention as fa
    m = {"causal": fa.CausalMask(sk - sq), None: None,
         "block_diffusion": fa.BlockDiffusionMask(sq // 2, 4),
         "block_diffusion_clean": fa.BlockDiffusionMask(sq, 4, noisy=False),
         }[mask]
    nq, nk = -(-sq // bq), -(-sk // bk)
    kv_len = sk if nk * bk != sk else None
    q = np.arange(nq * bq)[:, None]
    k = np.arange(nk * bk)[None, :]
    allowed = (k < sk) & (np.ones_like(q, bool) if m is None
                          else ~m.excluded(q, k))
    table = fa.tile_table(nq, nk, bq, bk, m, kv_len)
    strip = fa.STRIP
    for key_major in (False, True):
        computed = 0
        for qi, ki, kind in fa.tile_table(nq, nk, bq, bk, m, kv_len,
                                          key_major).tolist():
            if kind == fa.DEAD:
                continue
            tile = allowed[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk]
            whole = bq * bk
            if kind == fa.DIAGONAL and not bq % strip and not bk % strip:
                live = tile.reshape(bq // strip, strip, bk // strip,
                                    strip).any(axis=(1, 3))
                rows = live.T if key_major else live
                side_by_side = all(
                    np.ptp(np.flatnonzero(r)) + 1 == r.sum() for r in rows
                    if r.any())
                if not live.all() and side_by_side:
                    whole = int(live.sum()) * strip * strip
            computed += whole
        assert fa.tile_pairs(nq, nk, bq, bk, m, kv_len, key_major) == (
            computed, int(allowed.sum()))
    live = int((table[:, 2] != fa.DEAD).sum())
    assert computed <= live * bq * bk


def test_fit_publishes_the_counts_where_metrics_are_scraped(orca_ctx):
    """After a CPU ``fit`` the two gauges are in ``GET /metrics``."""
    import urllib.request

    from analytics_zoo_tpu.serving.broker import Broker
    from analytics_zoo_tpu.serving.frontend import FrontEnd
    est, x, y = _tiny_bert_estimator()
    _fit_and_wait(est, x, y)
    broker = Broker.launch(backend="python")
    try:
        with FrontEnd(broker.port, timeout=5.0).start() as fe:
            text = urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{fe.port}/metrics",
                headers={"Accept": "text/plain"}), timeout=10).read().decode()
    finally:
        broker.stop()
    assert 'zoo_step_held_values{executable="estimator_train_step"} 7' \
        in text
    assert 'zoo_step_elementwise_evals{executable="estimator_train_step",' \
        'kind="mask"} ' in text
    assert 'zoo_step_elementwise_evals{executable="estimator_train_step",' \
        'kind="erfc"} ' in text


def test_a_jitted_step_is_compiled_under_its_stable_name():
    """``jit_<name>`` names the compiled module, its events in a device
    trace and its entry in the persistent cache — whose key leaves
    metadata out, so a step whose named scopes changed must not share a
    name with the build that compiled the old ones."""
    import jax.numpy as jnp

    def step_fn(state, x, double):
        return state + (2 * x if double else x)

    jitted = telemetry.instrument_jit(
        step_fn, name="estimator_train_step", static_argnums=(2,))
    a = jnp.ones(3)
    assert float(jitted(a, a, True)[0]) == 3.0
    assert "module @jit_estimator_train_step" in \
        jitted.lower(a, a, True).as_text()
    assert step_fn.__name__ == "step_fn"        # the caller's is untouched
    by_keyword = telemetry.instrument_jit(name="other",
                                          static_argnames=("double",))
    assert float(by_keyword(step_fn)(a, a, double=False)[0]) == 2.0
    # without a name of its own the function's is kept
    bare = telemetry.instrument_jit(step_fn, static_argnums=(2,))
    assert "module @jit_step_fn" in bare.lower(a, a, True).as_text()


# ------------------------------------------- spans on the profiler's clock

def _host_annotations(log_dir, prefixes=("zoo:", "test:")):
    import jax
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    data = jax.profiler.ProfileData.from_file(found[-1])
    spans = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefixes):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    return spans


def test_fit_under_a_profiler_session_leaves_its_spans_in_the_xplane(
        orca_ctx, tmp_path):
    import jax
    est, x, y = _tiny_bert_estimator()
    _fit_and_wait(est, x, y)
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("test:traced"):
            est.fit((x, y), epochs=1, batch_size=8, summary_interval=2)
    spans = _host_annotations(str(tmp_path))
    by_name = {}
    for name, lo, hi in spans:
        by_name.setdefault(name, []).append((lo, hi))
    assert len(by_name["test:traced"]) == 1 and len(by_name["zoo:fit"]) == 1
    assert len(by_name["zoo:epoch"]) == 1
    assert len(by_name["zoo:epoch/first_batch"]) == 1
    assert len(by_name["zoo:epoch/flush"]) == 2     # 4 steps, every 2
    assert len(by_name["zoo:dispatch"]) == 4
    assert len(by_name["zoo:data_wait"]) == 5       # the last finds no batch
    assert len(by_name["zoo:fit/prepare"]) == 1

    def inside(inner, outer):
        return all(any(lo >= a and hi <= b for a, b in by_name[outer])
                   for lo, hi in by_name[inner])

    assert inside("zoo:fit", "test:traced")
    assert inside("zoo:fit/prepare", "zoo:fit")
    assert inside("zoo:epoch", "zoo:fit")
    for name in ("zoo:epoch/first_batch", "zoo:epoch/flush",
                 "zoo:dispatch", "zoo:data_wait"):
        assert inside(name, "zoo:epoch"), name
    # the first wait for data is the first-batch interval's
    first_wait = min(by_name["zoo:data_wait"])
    (fb,) = by_name["zoo:epoch/first_batch"]
    assert fb[0] <= first_wait[0] and first_wait[1] <= fb[1]


def test_tracer_span_annotates_only_once_jax_is_imported(monkeypatch):
    import contextlib
    import sys
    assert type(telemetry.annotation("x")).__name__ == "TraceAnnotation"
    monkeypatch.delitem(sys.modules, "jax")
    assert isinstance(telemetry.annotation("x"), contextlib.nullcontext)
    with telemetry.get_tracer().span("load", "job-1"):
        pass
    assert [s.name for s in telemetry.get_tracer().get("job-1")] == ["load"]


# ------------------------------------------------------ compile counters

def test_compile_counters_rise_on_a_new_shape_and_not_on_a_repeat(orca_ctx):
    import jax

    def counts():
        snap = telemetry.snapshot()
        events = snap.get("zoo_compile_events_total", {})
        seconds = snap.get("zoo_compile_seconds_total", {})
        cache = snap.get("zoo_compile_cache_total", {})
        return (events.get("stage=lower", 0),
                events.get("stage=backend_compile", 0),
                seconds.get("stage=lower", 0.0),
                sum(cache.values()))

    # numpy inputs: making a jax array would compile a program of its own
    f = jax.jit(lambda a: (a * 3.0 + 1.0).sum())
    before = counts()
    f(np.ones((5, 7), np.float32)).block_until_ready()
    first = counts()
    assert first[0] == before[0] + 1 and first[1] == before[1] + 1
    assert first[2] > before[2]
    assert first[3] == before[3] + 1        # one hit or one miss
    f(np.zeros((5, 7), np.float32)).block_until_ready()
    assert counts() == first                # the same shape: nothing
    f(np.ones((6, 7), np.float32)).block_until_ready()
    again = counts()
    assert again[0] == first[0] + 1 and again[1] == first[1] + 1
    # one listener per process, however often a context is made
    telemetry.install_compile_counters()
    f(np.ones((7, 7), np.float32)).block_until_ready()
    assert counts()[0] == again[0] + 1
