"""Device time by name (benchmark/lib/xplane_meta.py, scope_reduce.py
and the five readers over them): the wire-format reader against
``jax.profiler.ProfileData`` on the same bytes, the phase rule, the
sums on hand-made traces and on one BERT step recorded on the chip."""

import importlib
import json
import os

import pytest
from jax.profiler import ProfileData

from benchmark.lib import scope_reduce as sr
from benchmark.lib import trace_reduce as tr
from benchmark.lib import xplane_meta

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
READERS = ("train_forward_device_ms", "train_backward_device_ms",
           "train_optimizer_device_ms", "train_attention_device_ms",
           "train_scope_attributed_share")
PEAKS = {"bf16_flops_per_s": 200e12, "hbm_bytes_per_s": 800e9}

STAT_NAMES = {1: "tf_op", 2: "flops", 3: "hlo_category", 4: "program_id",
              5: "convolution fusion", 6: "occupancy", 7: "delta",
              8: "bytes_accessed", 9: "Hlo Proto", 10: "loop fusion"}

FWD = "jit(step)/jvp(Net)/enc/encoder_0/attention/attention_einsum/dot_general:"
BWD = "jit(step)/transpose(jvp(Net))/enc/encoder_1/ffn/dot_general:"
OPT = "jit(step)/optimizer/mul:"
GRAD_SUM = "jit(step)/transpose(jvp(Net))/enc/encoder_1/ffn/reduce_sum:"


def _stat_metadata():
    return "".join(
        f'stat_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }}\n'
        for k, v in STAT_NAMES.items())


def _event_metadata(key, name, tf_op=None, category=5, flops=None,
                    bytes_accessed=None, extra=""):
    stats = f"stats {{ metadata_id: 3 ref_value: {category} }}\n"
    if tf_op is not None:
        stats += f'stats {{ metadata_id: 1 str_value: "{tf_op}" }}\n'
    if flops is not None:
        stats += f"stats {{ metadata_id: 2 uint64_value: {flops} }}\n"
    if bytes_accessed is not None:
        stats += (f"stats {{ metadata_id: 8 uint64_value: {bytes_accessed} "
                  "}\n")
    return (f'event_metadata {{ key: {key} value {{ id: {key} '
            f'name: "{name}" {stats}{extra} }} }}\n')


def _device_plane(number, ops, events):
    """``ops``: event metadata text; ``events``: (metadata id, start_ns,
    duration_ns) on line ``XLA Ops``."""
    lines = "".join(
        f"events {{ metadata_id: {m} offset_ps: {int(s * 1000)} "
        f"duration_ps: {int(d * 1000)} stats {{ metadata_id: 4 "
        "uint64_value: 42 } }\n" for m, s, d in events)
    return (f'planes {{ id: {number + 1} name: "/device:TPU:{number}"\n'
            f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000\n{lines} }}\n'
            f"{ops}{_stat_metadata()} }}\n")


HLO_FWD = "%fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %p), kind=kOutput"
HLO_BWD = "%fusion.2 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %q), kind=kOutput"
HLO_OPT = "%multiply_add_fusion = f32[8]{0} fusion(f32[8]{0} %w), kind=kLoop"
HLO_COPY = "%copy-done.3 = f32[8]{0} copy-done((f32[8]{0}, u32[]) %cs)"
HLO_ALLREDUCE = "%all-reduce.5 = f32[8]{0} all-reduce(f32[8]{0} %g)"
HLO_WHILE = "%while.7 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t)"


def _step_ops(with_names=True, with_optimizer=True):
    def name(tf_op):
        return tf_op if with_names else None

    return (
        _event_metadata(1, HLO_FWD, name(FWD), flops=4_000_000,
                        bytes_accessed=2000,
                        extra="stats { metadata_id: 6 double_value: 0.5 } "
                              "stats { metadata_id: 7 int64_value: -3 }")
        + _event_metadata(2, HLO_BWD, name(BWD), flops=8_000_000,
                          bytes_accessed=4000)
        + _event_metadata(3, HLO_OPT, name(OPT if with_optimizer
                                           else "jit(step)/mul:"),
                          category=10, flops=16, bytes_accessed=8000)
        + _event_metadata(4, HLO_COPY, None, category=10)
        + _event_metadata(5, HLO_ALLREDUCE, name(GRAD_SUM), category=10)
        + _event_metadata(6, HLO_WHILE, None, category=10))


# two steps on each of two chips; the while wrapper covers a whole step
STEP_EVENTS = [(6, 0, 100), (1, 0, 20), (2, 20, 40), (5, 60, 10),
               (3, 70, 10), (4, 85, 5),
               (6, 200, 100), (1, 200, 20), (2, 220, 40), (5, 260, 10),
               (3, 270, 10), (4, 285, 5)]
BUSY_NS_PER_STEP = 20 + 40 + 10 + 10 + 5


def _xspace(**kwargs):
    text = (_device_plane(0, _step_ops(**kwargs), STEP_EVENTS)
            + _device_plane(1, _step_ops(**kwargs), STEP_EVENTS)
            + 'planes { id: 9 name: "/host:CPU" lines { id: 7 name: "main" '
              "events { metadata_id: 1 offset_ps: 0 duration_ps: 10 } } "
              'event_metadata { key: 1 value { id: 1 name: "fit" } } }\n')
    return ProfileData.text_proto_to_serialized_xspace(text)


def _written(tmp_path, data: bytes) -> str:
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(data)
    return str(path)


# ------------------------------------------------------------------ #
# the wire-format reader                                             #
# ------------------------------------------------------------------ #
def test_metadata_reader_agrees_with_profile_data():
    data = _xspace()
    meta = xplane_meta.read_metadata(data)
    profile = ProfileData.from_serialized_xspace(data)
    assert list(meta) == [p.name for p in profile.planes] == [
        "/device:TPU:0", "/device:TPU:1", "/host:CPU"]
    for plane in profile.planes:
        seen = {e.name for line in plane.lines for e in line.events}
        assert seen and seen <= set(meta[plane.name])
    # ProfileData shows the event's own statistic and not one of the
    # metadata's: the reason this reader exists
    event = next(iter(next(iter(profile.planes)).lines)).events
    assert [dict(e.stats) for e in event][0] == {"program_id": 42}


@pytest.mark.parametrize("stat,value", [
    ("tf_op", FWD),                          # str
    ("flops", 4_000_000),                    # uint64
    ("occupancy", 0.5),                      # double
    ("delta", -3),                           # int64, negative
    ("hlo_category", "convolution fusion"),  # ref, resolved to its name
])
def test_metadata_reader_stat_kinds(stat, value):
    meta = xplane_meta.read_metadata(_xspace())
    for plane in ("/device:TPU:0", "/device:TPU:1"):
        assert meta[plane][HLO_FWD][stat] == value
    assert meta["/device:TPU:0"][HLO_COPY] == {"hlo_category": "loop fusion"}
    assert meta["/host:CPU"] == {"fit": {}}


def test_metadata_reader_refuses_other_bytes():
    with pytest.raises((ValueError, IndexError)):
        xplane_meta.read_metadata(b"\x0b\x0c not a protobuf \xff\xff")


# ------------------------------------------------------------------ #
# names -> phase, module, layer                                      #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("hlo,op_name,want", [
    ("all-reduce.5", GRAD_SUM, "collective"),     # by HLO name, not op_name
    ("all-gather-start.1", "", "collective"),
    ("fusion.9", "jit(step)/optimizer/mul", "optimizer"),
    ("fusion.9", "jit(step)/optimizer/jit(_where)/select_n", "optimizer"),
    ("fusion.2", BWD, "backward"),
    ("fusion.2", "jit(step)/transpose(jvp(loss))/mul", "backward"),
    # a forward op rematerialised for the backward pass is named so
    ("fusion.3", "jit(step)/transpose(jvp(Net))/checkpoint/enc/dot_general",
     "backward"),
    ("fusion.1", FWD, "forward"),
    ("fusion.1", "jit(step)/jvp(loss)/reduce_sum", "forward"),
    ("fusion.1", "jit(step)/grad_accum/while/body/closed_call/jvp(Net)/a/add",
     "forward"),
    ("copy-done.3", "", "other"),                 # no op_name
    ("fusion.4", "jit(step)/add", "other"),       # the loss-sum add
    ("transpose.1", "jit(transpose)/transpose", "other"),
])
def test_phase_rule(hlo, op_name, want):
    assert sr.phase(hlo, op_name) == want


@pytest.mark.parametrize("op_name,path,starred,layer", [
    ("jit(step)/jvp(BERTForSQuAD)/squad/bert/encoder_3/attention/qkv/"
     "dot_general", "squad/bert/encoder_3/attention/qkv",
     "squad/bert/encoder_*/attention/qkv", "squad/bert/encoder_3"),
    ("jit(step)/transpose(jvp(_NormalizedBackbone))/backbone/stage2_block1/"
     "bn3/mul", "backbone/stage2_block1/bn3", "backbone/stage2_block*/bn3",
     "backbone/stage2_block1"),
    ("jit(step)/jvp(M)/ffn/jit(gelu)/tanh", "ffn", "ffn", "ffn"),
    ("jit(step)/jvp(M)/enc/attention_einsum/bhqk,bhkd->bhqd/dot_general;"
     "jit(step)/jvp(M)/enc/other/add", "enc/attention_einsum/bhqk,bhkd->bhqd",
     "enc/attention_einsum/bhqk,bhkd->bhqd",
     "enc/attention_einsum/bhqk,bhkd->bhqd"),
    ("jit(step)/optimizer/jit(_where)/select_n", "optimizer", "optimizer",
     "optimizer"),
    ("jit(step)/jvp(loss)/jit(log_softmax)/reduce_max", "loss", "loss",
     "loss"),
    ("jit(step)/transpose(jvp(loss))/mul", "loss", "loss", "loss"),
    ("jit(step)/jvp(Net)/select_n", "Net", "Net", "Net"),
    ("jit(step)/mul", "step", "step", "step"),
    ("transpose(jvp(Net))/head/reduce_sum", "head", "head", "head"),
    ("", sr.NO_OP_NAME, sr.NO_OP_NAME, sr.NO_OP_NAME),
])
def test_module_path(op_name, path, starred, layer):
    assert sr.module_path(op_name) == path
    assert sr.without_layer_index(path) == starred
    assert sr.layer_of(path) == layer


# ------------------------------------------------------------------ #
# the sums                                                           #
# ------------------------------------------------------------------ #
def test_phase_sums_equal_busy_time_on_hand_made_trace(tmp_path):
    path = _written(tmp_path, _xspace())
    scoped = sr.load_scoped(path)
    assert scoped["op_name_from"] == "tf_op"
    assert sorted(scoped["devices"]) == ["0", "1"]
    # one row per instruction, the while wrapper left out
    assert sorted(r[sr.HLO] for r in scoped["devices"]["0"]) == [
        "all-reduce.5", "copy-done.3", "fusion.1", "fusion.2",
        "multiply_add_fusion"]
    assert all(r[sr.RUNS] == 2 for r in scoped["devices"]["1"])

    reduced = sr.reduce_scopes(scoped, steps=2, peaks=PEAKS)
    busy = tr.reduce_trace(tr.load_xplane(path))
    assert reduced["total_ms"] == pytest.approx(busy["busy_s"] * 1e3 / 2)
    assert reduced["total_ms"] == pytest.approx(BUSY_NS_PER_STEP * 1e-6)
    assert reduced["phases_ms"] == pytest.approx({
        "forward": 20e-6, "backward": 40e-6, "optimizer": 10e-6,
        "collective": 10e-6, "other": 5e-6})
    assert sum(reduced["phases_ms"].values()) == pytest.approx(
        reduced["total_ms"])
    assert reduced["attributed_share"] == pytest.approx(80 / 85)
    assert reduced["named_share"] == pytest.approx(80 / 85)
    assert reduced["attention_ms"] == pytest.approx(
        {"attention_einsum": 20e-6})
    assert reduced["other_ops"] == [["copy-done.3", pytest.approx(5e-6)]]

    rows = {r["scope"]: r for r in reduced["modules"]}
    assert list(rows) == ["enc/encoder_*/ffn",
                          "enc/encoder_*/attention/attention_einsum",
                          "optimizer", sr.NO_OP_NAME]
    ffn = rows["enc/encoder_*/ffn"]        # the matmul and the all-reduce
    assert ffn["backward_ms"] == pytest.approx(40e-6)
    assert ffn["total_ms"] == pytest.approx(50e-6)
    assert ffn["share"] == pytest.approx(50 / 85)
    assert ffn["gflops"] is None           # the all-reduce has no counts
    attn = rows["enc/encoder_*/attention/attention_einsum"]
    assert attn["forward_ms"] == pytest.approx(20e-6)
    assert attn["hlo_category"] == "convolution fusion"
    assert attn["gflops"] == pytest.approx(4e6 / 1e9)
    assert attn["mbytes"] == pytest.approx(2000 / 1e6)
    # 4 MFLOP at 200 TFLOP/s = 20 ns of the 20 ns taken; 2000 B at 800
    # GB/s = 2.5 ns
    assert attn["flops_share"] == pytest.approx(1.0)
    assert attn["bytes_share"] == pytest.approx(0.125)
    assert [r["scope"] for r in reduced["layers"]] == [
        "enc/encoder_1", "enc/encoder_0", "optimizer", sr.NO_OP_NAME]


def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _field(number, payload: bytes) -> bytes:
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _hlo_proto(instructions) -> bytes:
    """A serialized ``HloProto`` made by hand, independent of the reader:
    ``hlo_module`` 1 > ``computations`` 3 > ``instructions`` 2 >
    ``name`` 1, ``opcode`` 2, ``metadata`` 7 > ``op_name`` 2."""
    body = b""
    for name, op_name in instructions:
        instruction = _field(1, name.encode()) + _field(2, b"fusion")
        if op_name is not None:
            instruction += _field(7, _field(1, b"fusion")
                                  + _field(2, op_name.encode()))
        body += _field(2, instruction)
    computation = _field(1, b"main") + body
    module = _field(1, b"jit_step") + _field(3, computation)
    return _field(1, module)


def test_op_names_from_the_programs_when_the_planes_have_none(tmp_path):
    proto = _hlo_proto([("fusion.1", FWD.rstrip(":")),
                        ("fusion.2", BWD.rstrip(":")),
                        ("multiply_add_fusion", OPT.rstrip(":")),
                        ("copy-done.3", None)])
    assert xplane_meta.hlo_op_names(proto) == {
        "fusion.1": FWD.rstrip(":"), "fusion.2": BWD.rstrip(":"),
        "multiply_add_fusion": OPT.rstrip(":")}
    escaped = "".join(f"\\{b:03o}" for b in proto)
    text = (_device_plane(0, _step_ops(with_names=False), STEP_EVENTS)
            + 'planes { id: 8 name: "/host:metadata" '
              'event_metadata { key: 1 value { id: 1 name: "jit_step(42)" '
              f'stats {{ metadata_id: 9 bytes_value: "{escaped}" }} }} }}\n'
              f"{_stat_metadata()} }}\n")
    path = _written(
        tmp_path, ProfileData.text_proto_to_serialized_xspace(text))
    scoped = sr.load_scoped(path)
    assert scoped["op_name_from"] == "hlo_proto"
    reduced = sr.reduce_scopes(scoped, steps=2)
    assert reduced["phases_ms"] == pytest.approx({
        "forward": 20e-6, "backward": 40e-6, "optimizer": 10e-6,
        "collective": 10e-6, "other": 5e-6})
    assert reduced["modules"][0]["flops_share"] is None    # no peaks given


# ------------------------------------------------------------------ #
# the readers                                                        #
# ------------------------------------------------------------------ #
def _ctx(trace):
    return {"trace": trace, "cell": {"name": "hand-made.cell"},
            "window": {"steps_per_epoch": 2}, "device_kind": "TPU v5 lite"}


@pytest.mark.parametrize("reader", READERS)
@pytest.mark.parametrize("why", ["no_device_plane", "no_trace_file"])
def test_reader_gives_nothing_without_a_device_trace(reader, why,
                                                     monkeypatch):
    """A CPU rehearsal's trace has no device plane (the runner hands
    ``trace: None``); a trace that is not there is no reason to raise
    either."""
    monkeypatch.setattr(tr, "find_xplane", lambda trace_dir: None)
    module = importlib.import_module(f"benchmark.layer_metrics.{reader}")
    trace = None if why == "no_device_plane" else {"busy_s": 1.0}
    assert module.read(_ctx(trace)) is None


@pytest.mark.parametrize("reader,want", [
    ("train_forward_device_ms", 20e-6),
    ("train_backward_device_ms", 40e-6),
    ("train_optimizer_device_ms", 10e-6),
    ("train_attention_device_ms", 20e-6),
    ("train_scope_attributed_share", 100 * 80 / 85),
])
def test_reader_on_hand_made_trace(reader, want, tmp_path, monkeypatch,
                                   capsys):
    path = _written(tmp_path, _xspace())
    monkeypatch.setattr(tr, "find_xplane", lambda trace_dir: path)
    module = importlib.import_module(f"benchmark.layer_metrics.{reader}")
    ctx = _ctx({"busy_s": 2 * BUSY_NS_PER_STEP * 1e-9})
    assert module.read(ctx) == pytest.approx(want)
    # the tables go to standard error, once per trace and process
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "scope_reduce: by module" in captured.err
    assert "scope_reduce: by layer" in captured.err
    assert "largest ops in 'other'" in captured.err
    assert "copy-done.3" in captured.err
    module.read(ctx)
    assert "scope_reduce: by module" not in capsys.readouterr().err


def test_optimizer_reader_says_when_no_op_carries_the_scope(
        tmp_path, monkeypatch, capsys):
    """How a stale executable (or the parent's program) shows: nothing,
    and a line on standard error, not 0 ms."""
    path = _written(tmp_path, _xspace(with_optimizer=False))
    monkeypatch.setattr(tr, "find_xplane", lambda trace_dir: path)
    from benchmark.layer_metrics import (train_forward_device_ms,
                                         train_optimizer_device_ms)

    ctx = _ctx({"busy_s": 2 * BUSY_NS_PER_STEP * 1e-9})
    assert train_optimizer_device_ms.read(ctx) is None
    assert "carries the scope 'optimizer'" in capsys.readouterr().err
    assert train_forward_device_ms.read(ctx) == pytest.approx(20e-6)


def test_no_tensorflow_in_the_benchmark():
    bench = os.path.dirname(os.path.dirname(os.path.abspath(sr.__file__)))
    for folder, _, files in os.walk(bench):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as f:
                    text = f.read()
                assert "import tensorflow" not in text, name
                assert "xplane_pb2" not in text.replace(
                    "``xplane_pb2``", ""), name


# ------------------------------------------------------------------ #
# one BERT step recorded on the chip                                 #
# ------------------------------------------------------------------ #
def test_recorded_bert_step():
    """The rows ``load_scoped`` made of one train step of the one-chip
    BERT cell, traced on a TPU v5 lite from a fresh compile cache (my
    chip run, PR 24)."""
    with open(os.path.join(
            DATA, "recorded_v5e_1chip_bert_step_meta.json")) as f:
        recorded = json.load(f)
    assert recorded["op_name_from"] == "tf_op"
    reduced = sr.reduce_scopes(recorded, steps=1,
                               peaks={"bf16_flops_per_s": 197e12,
                                      "hbm_bytes_per_s": 819e9})
    want = recorded["expected"]
    assert reduced["total_ms"] == pytest.approx(want["total_ms"], rel=1e-9)
    assert sum(reduced["phases_ms"].values()) == pytest.approx(
        reduced["total_ms"])
    assert reduced["phases_ms"] == pytest.approx(want["phases_ms"], rel=1e-9)
    assert list(reduced["attention_ms"]) == ["attention_einsum"]
    assert reduced["attention_ms"]["attention_einsum"] == pytest.approx(
        want["attention_einsum_ms"], rel=1e-9)
    assert [r["scope"] for r in reduced["modules"][:5]] == want[
        "top_modules"]
    assert len({r["scope"] for r in reduced["layers"]
                if r["scope"].startswith("squad/bert/encoder_")}) == 12
    # every matmul row's FLOPs fit under the chip's peak
    assert all(r["flops_share"] <= 1.0 for r in reduced["modules"]
               if r["flops_share"] is not None)
