"""The trace reduction: its interval arithmetic, the span join, and one
small trace recorded on a TPU v5e."""
import glob
import gzip
import os

from benchkit import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_op_name_of_a_tpu_event():
    assert tr._op_name("%fusion.2 = u32[3]{0} fusion(), kind=kLoop") == \
        "fusion.2"
    assert tr._op_name("dot_general.1") == "dot_general.1"


def test_outermost_span_wins():
    path = "jit(f)/shard_map/wire/quantize_pack/pallas/fl/apply/mul"
    assert tr.outermost_span(path) == "wire/quantize_pack"
    assert tr.outermost_span("fl/local_steps/dot") == "fl/local_steps"
    assert tr.outermost_span("jit(f)/other/add") is None
    assert tr.outermost_span(None) is None


def test_parse_hlo_op_names():
    text = ('%fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, '
            'metadata={op_name="jit(step)/fl/apply/add" source_file="x"}\n'
            '%copy.1 = f32[4]{0} copy(%p)\n')
    assert tr.parse_hlo_op_names(text) == {
        "fusion.3": "jit(step)/fl/apply/add"}


def test_union_and_gaps():
    iv = [(0, 10), (5, 20), (30, 40)]
    assert tr.union_length(iv) == 30
    assert tr.gaps(iv, 0, 50) == [(20, 30), (40, 50)]
    assert tr.gaps([], 0, 5) == [(0, 5)]


def test_leaf_ops_drops_enclosing_ops():
    outer = tr.Op("while.1", "m", 0, 100)
    a = tr.Op("fusion.1", "m", 10, 20)
    b = tr.Op("fusion.2", "m", 30, 60)
    c = tr.Op("fusion.3", "m", 120, 130)
    assert [o.name for o in tr.leaf_ops([outer, a, b, c])] == [
        "fusion.1", "fusion.2", "fusion.3"]


def test_name_gap_takes_the_innermost_covering_span():
    spans = [(0, 100, "bench/window"), (10, 60, "bench/run_rounds"),
             (20, 40, "bench/input_prep")]
    assert tr.name_gap((22, 38), spans) == "bench/input_prep"
    assert tr.name_gap((45, 58), spans) == "bench/run_rounds"
    assert tr.name_gap((70, 90), spans) == "host: outside any span"


CHIP = os.path.join(DATA, "tpu_v5e_fl_round")


def test_recorded_chip_trace(tmp_path):
    """A tiny FL round traced on one TPU v5e: nearly all device op time
    carries an op_name, the join attributes most of it to the round's
    spans (coverage is over all op time), and busy <= window."""
    import json
    import shutil
    meta = json.load(open(os.path.join(CHIP, "meta.json")))
    with gzip.open(os.path.join(CHIP, "trace.xplane.pb.gz")) as src, \
            open(tmp_path / "trace.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    hlo = {}
    for p in glob.glob(os.path.join(CHIP, "*.hlo.txt.gz")):
        with gzip.open(p, "rt") as f:
            hlo[os.path.basename(p)[:-len(".hlo.txt.gz")]] = f.read()

    class Dev:
        device_kind = "TPU v5 lite"
    ctx = tr.reduce(str(tmp_path), {"rounds": meta["rounds"], "hlo": hlo,
                                    "work": {}}, [Dev()])
    assert 0 < ctx["busy_s"] <= ctx["window_s"]
    assert ctx["coverage"] >= meta["min_coverage"]
    assert ctx["provenanced"] >= meta["min_provenanced"]
    assert ctx["coverage"] <= ctx["provenanced"] <= 1.0
    assert "fl/local_steps" in ctx["span_s"]
    assert any(s.startswith("wire/") for s in ctx["span_s"])
    assert len(ctx["breakdown"]["device_ops"]) == 10
    assert sum(ctx["span_s"].values()) <= ctx["op_s"] + 1e-12
