"""Tier-1 tests of the readers PR 26 adds (benchmarks/harness/scopes.py and
the five metrics on it), on hand-made traces: what the three device readers
attribute to a layer kind and divide by, when they return None and never 0,
what the two host readers take from the host plane, and that every entry
PR 26 adds to BENCHMARK.json resolves. Like the rest of tests/benchmark/
they drive nothing of the program's insides beyond its two pure functions
`op_scopes`/`scope_of`, which a program without layer scopes lacks: then the
readers return None (tested by hiding the module).
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import loader, scopes                    # noqa: E402
from benchmarks.harness.window import TRACED                     # noqa: E402

BENCH = loader.benchmark()
STAGED, DP4 = "resnet50.train-staged", "resnet50.train-dp4"
NEW = {"train_bn_device_ms": [STAGED, DP4],
       "train_conv_device_ms": [STAGED, DP4],
       "train_update_device_ms": [STAGED, DP4],
       "train_dispatch_host_ms": [STAGED],
       "dp_stage_host_ms": [DP4],
       "allreduce_device_ms": [DP4]}


def read(name, ctx):
    return loader.metric_reader(name)(ctx)


def hlo(instr, kind="fusion"):
    """An operation event's name as the TPU writes it."""
    return f"%{instr} = bf16[8,8]{{1,0}} {kind}(bf16[8,8]{{1,0}} %p)"


def text_of(table):
    """A compiled module's text whose instructions carry `table`'s paths;
    `fusion.3` calls a computation that holds a batch-norm operation."""
    body = "".join(
        f'  %{name} = f32[8]{{0}} fusion(%p), kind=kLoop, '
        f'calls=%fused.{name}, metadata={{op_name="{path}"}}\n'
        for name, path in table.items())
    return ('%fused.fusion.3 (p: f32[8]) -> f32[8] {\n'
            '  %c = f32[8]{0} convolution(%p), metadata={op_name='
            '"jit(step)/jvp(convolution.stem_conv)/conv_general_dilated"}\n'
            '  ROOT %r = f32[8]{0} reduce(%c), metadata={op_name='
            '"jit(step)/jvp(batchnorm.stem_bn)/reduce_sum"}\n}\n\n'
            'ENTRY %main (p: f32[8]) -> f32[8] {\n' + body + '}\n')


def ctx_of(ops, modules, host=(), table=None, t0=0, t1=10_000):
    ctx = {"trace": {"trace": {"devices": {"0": {"ops": list(ops),
                                                 "modules": list(modules)}},
                               "host": [[TRACED, t0, t1 - t0], *host]},
                     "t0": t0, "t1": t1}}
    if table is not None:
        ctx["step_text"] = text_of(table) if table else None
    return ctx


TABLE = {
    "fusion.1": "jit(step)/jvp(batchnorm.stem_bn)/reduce_sum",
    "fusion.2": "jit(step)/transpose(jvp(batchnorm.stem_bn))/reduce_sum",
    "fusion.3": "jit(step)/jvp(convolution.stem_conv)/conv_general_dilated",
    "fusion.4": "jit(step)/transpose(jvp(convolution.stem_conv))/conv",
    "fusion.5": "jit(step)/update/sub",
    "fusion.6": "jit(step)/jvp(activation.stem_act)/max",
    "all-reduce.7": "jit(step)/transpose(jvp(batchnorm.stem_bn))/reduce_sum",
    "all-reduce.8": "jit(step)/transpose(jvp(convolution.stem_conv))/conv",
    "fusion.9": "broadcast.202",
}


def two_steps():
    """Two whole runs of `jit_step` (1,000 ns each) and a third that
    straddles the window's end, a smaller module between them."""
    def step(at):
        return [[hlo("fusion.1"), at, 100], [hlo("fusion.2"), at + 100, 200],
                [hlo("fusion.3"), at + 300, 250],
                [hlo("fusion.4"), at + 550, 150],
                [hlo("fusion.5"), at + 700, 50],
                [hlo("fusion.6"), at + 750, 100],
                [hlo("all-reduce.7", "all-reduce"), at + 850, 30],
                [hlo("all-reduce.8", "all-reduce"), at + 880, 20],
                [hlo("fusion.9"), at + 900, 40],
                [hlo("copy.77", "copy"), at + 940, 60]]
    ops = step(1000) + step(3000) + step(9500) \
        + [[hlo("fusion.1"), 2200, 500]]       # another module's fusion.1
    modules = [["jit_step(1)", 1000, 1000], ["jit_other(2)", 2200, 500],
               ["jit_step(1)", 3000, 1000], ["jit_step(1)", 9500, 1000]]
    return ops, modules


# --------------------------------------------------- the device readers
def test_forward_and_backward_scopes_add_up_and_divide_by_the_steps():
    ctx = ctx_of(*two_steps(), table=TABLE)
    # per step: bn 100 + 200 + the all-reduce scoped in it 30; conv 250 +
    # 150 + 20; update 50 -- over the two WHOLE runs of the step module
    assert read("train_bn_device_ms", ctx) == pytest.approx(330e-6)
    assert read("train_conv_device_ms", ctx) == pytest.approx(420e-6)
    assert read("train_update_device_ms", ctx) == pytest.approx(50e-6)
    lt = scopes.layer_times(ctx)
    assert lt["steps"] == 2
    assert lt["step_ms"] == pytest.approx(1000e-6)
    assert lt["ms"][("batchnorm", "forward")] == pytest.approx(100e-6)
    assert lt["ms"][("batchnorm", "backward")] == pytest.approx(230e-6)
    # found in the table: everything but the copy; `broadcast.202` is found
    # and has no scope
    assert lt["coverage"] == pytest.approx(0.94)
    assert lt["collective_ms"] == {"batchnorm": pytest.approx(30e-6),
                                   "convolution": pytest.approx(20e-6)}
    assert sum(lt["ms"].values()) < lt["step_ms"]
    # fusion.3 is rooted in the convolution and holds a batch-norm reduce
    assert lt["holds_batchnorm_ms"] == {
        ("convolution", "forward"): pytest.approx(250e-6)}


def test_the_step_is_compiled_once_a_run(monkeypatch):
    calls = []
    monkeypatch.setattr(scopes, "compiled_text",
                        lambda cell: calls.append(cell) or text_of(TABLE))
    ctx = dict(ctx_of(*two_steps()), cell={"name": "x"})
    for name in ("train_bn_device_ms", "train_conv_device_ms",
                 "train_update_device_ms"):
        assert read(name, ctx) > 0
    assert calls == [{"name": "x"}]


def test_under_ninety_percent_joined_reads_none_never_zero(capsys):
    ops, modules = two_steps()
    ops += [[hlo("copy.78", "copy"), 1990, 9], [hlo("copy.78", "copy"),
                                               3990, 9]]
    # 69 of 1,009 ns a step are not in the table: still read
    assert read("train_bn_device_ms",
                ctx_of(ops, modules, table=TABLE)) is not None
    table = {k: v for k, v in TABLE.items() if k != "fusion.4"}
    ctx = ctx_of(ops, modules, table=table)
    for name in ("train_bn_device_ms", "train_conv_device_ms",
                 "train_update_device_ms"):
        assert read(name, ctx) is None
    assert "under 90%" in capsys.readouterr().err


@pytest.mark.parametrize("ops,modules,table", [
    ([], [], TABLE),                                    # an empty trace
    (two_steps()[0], [], TABLE),                        # no module line
    (*two_steps(), {}),                                 # no text
    (*two_steps(), None)])                              # no scopes at all
def test_nothing_to_read_is_none(monkeypatch, ops, modules, table):
    monkeypatch.setattr(scopes, "compiled_text", lambda cell: None)
    ctx = dict(ctx_of(ops, modules, table=table), cell={})
    for name in ("train_bn_device_ms", "train_conv_device_ms",
                 "train_update_device_ms"):
        assert read(name, ctx) is None


def test_a_kind_the_step_does_not_have_is_none():
    table = {k: v for k, v in TABLE.items() if "update" not in v}
    table["fusion.5"] = "jit(step)/jvp(activation.stem_act)/max"
    ctx = ctx_of(*two_steps(), table=table)
    assert read("train_update_device_ms", ctx) is None
    assert read("train_bn_device_ms", ctx) is not None


def test_a_program_without_op_scopes_gives_no_text(monkeypatch, capsys):
    """The parent of PR 26: optimize/profiler.py has no `op_scopes`."""
    import deeplearning4j_tpu.optimize.profiler as P
    monkeypatch.delattr(P, "op_scopes")
    assert scopes.compiled_text({"name": "x"}) is None
    assert "no op_scopes" in capsys.readouterr().err


def test_allreduce_device_ms_counts_the_collectives_of_a_step():
    ctx = ctx_of(*two_steps(), table=TABLE)
    # 30 + 20 in each of the two steps whose collectives lie inside the
    # window, over the two whole runs
    assert read("allreduce_device_ms", ctx) == pytest.approx(50e-6)


# ----------------------------------------------------- the host readers
def host_plane():
    """Six steps of stage (40) then dispatch (100, 100, 300, 100, 100,
    100); the first starts before the window and the last ends after it."""
    host = []
    for i, d in enumerate([100, 100, 300, 100, 100, 100]):
        at = 900 + 1000 * i
        host += [["parallel.stage", at, 40],
                 ["parallel.dispatch", at + 150, d],
                 ["train.dispatch#k=1#", at + 150, d + 10],
                 ["parallel.checkpoint", at + 400, 5]]
    return host


def test_host_readers_take_the_median_inside_the_window_only():
    ctx = ctx_of([], [], host=host_plane(), t0=1000, t1=6000)
    # whole inside [1000, 6000): the dispatches of steps 0..4 (the first
    # step's stage began at 900, outside: that step has no pair)
    assert [d for _, d in scopes.host_spans(ctx, "parallel.dispatch")] == \
        [100, 100, 300, 100, 100]
    assert read("train_dispatch_host_ms", ctx) == pytest.approx(110e-6)
    # stage + dispatch of steps 1..4: 140, 340, 140, 140
    assert read("dp_stage_host_ms", ctx) == pytest.approx(140e-6)


def test_host_readers_without_the_programs_annotations_are_none():
    ctx = ctx_of([], [], host=[["bench.fit", 1000, 100],
                               ["PjitFunction(step)", 1010, 50]])
    assert read("train_dispatch_host_ms", ctx) is None
    assert read("dp_stage_host_ms", ctx) is None
    # a dispatch with no stage before it is not a step of the wrapper
    ctx = ctx_of([], [], host=[["parallel.dispatch", 1000, 100]])
    assert read("dp_stage_host_ms", ctx) is None


# ------------------------------------------ what PR 26 adds to the file
@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metric_is_declared_and_resolves(name):
    (m,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert m["workloads"] == NEW[name]
    assert m["unit"] == "ms" and m["better"] == "lower"
    assert m["moves"] == "images_per_s"
    assert m["source"] == ("program_span" if "host" in name
                           else "device_trace")
    assert m["layer"] == ("parallelism" if name in (
        "dp_stage_host_ms", "allreduce_device_ms") else "trainer containers")
    assert callable(loader.metric_reader(name))
    for cell in NEW[name]:
        assert name in {x["name"] for x in loader.cell(cell)["per_layer"]}


def test_the_four_chip_cell_is_declared_as_the_issue_gives_it():
    (w,) = [w for w in BENCH["workloads"] if w["name"] == DP4]
    assert (w["config"], w["traffic"], w["chips"]) == ("resnet50",
                                                       "train-dp4", 4)
    cell = loader.cell(DP4)
    assert cell["traffic"]["ring"] == 8
    assert cell["config"]["trainer"]["batch_per_chip"] == 256
    assert {m["name"] for m in cell["end_to_end"]} == {"images_per_s",
                                                       "setup_s"}
    assert {m["name"] for m in cell["per_layer"]} == {
        "train_step_device_ms", "train_mfu", "train_bn_device_ms",
        "train_conv_device_ms", "train_update_device_ms",
        "dp_stage_host_ms", "allreduce_device_ms"}
    # what was there stays first, in its order
    assert [w["name"] for w in BENCH["workloads"]][0] == STAGED
    assert [m["name"] for m in BENCH["per_layer"]][:2] == [
        "train_step_device_ms", "train_mfu"]
