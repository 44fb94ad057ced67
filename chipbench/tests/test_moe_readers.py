"""The readers of the expert layers' device time on a small synthetic
trace: ops under the ``moe`` scope, and the grouped products that a TPU
trace shows as Mosaic kernels without any scope (``ragged-dot-*``), which
the architecture module names in ``UNSCOPED``."""
import json
import types

import pytest

from chipbench import run
from chipbench.lib import layer_ops, work
from chipbench.lib import host_spans as hs
from chipbench.lib.context import RunContext
from chipbench.run import reader
from chipbench.tests.test_host_spans import write_trace

ARCH = run.load("archs", "mla_moe")
FILE = json.loads((run.BENCH / "configs" / "deepseek-v2-lite-16b.json")
                  .read_text())
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
DEC, PRE = "jit(serve_decode)", "jit(serve_prefill)"
RAGGED = {"tf_op": "ragged-dot-none:"}
# (line, name, start ns, end ns, stats): one prefill of 512 tokens, two
# decode steps
EVENTS = [
    ("host", "cb.prefill", 50, 900, {"tokens": 512}),
    ("XLA Modules", "jit_serve_prefill(1)", 1000, 21000, {}),
    ("XLA Ops", "%fusion.1", 1000, 5000,
     {"tf_op": f"{PRE}/attention/dot_general"}),
    ("XLA Ops", "%fusion.2", 5000, 6000,
     {"tf_op": f"{PRE}/moe/router/dot_general"}),
    ("XLA Ops", "%ragged-dot-none.1", 6000, 15000, RAGGED),
    ("XLA Ops", "%fusion.3", 15000, 18000,
     {"tf_op": f"{PRE}/moe/shared_experts/dot_general"}),
    ("XLA Ops", "%fusion.4", 18000, 20000,
     {"tf_op": f"{PRE}/lm_head/dot_general"}),
    ("host", "cb.decode", 21500, 21900, {}),
    ("XLA Modules", "jit_serve_decode(2)", 22000, 32000, {}),
    ("XLA Ops", "%fusion.5", 22000, 24000,
     {"tf_op": f"{DEC}/attention/latent/dot_general"}),
    ("XLA Ops", "%fusion.6", 24000, 25000,
     {"tf_op": f"{DEC}/moe/router/dot_general"}),
    ("XLA Ops", "%ragged-dot-none.2", 25000, 30000, RAGGED),
    ("XLA Ops", "%copy.1", 30000, 31000, {"tf_op": ""}),
    ("host", "cb.decode", 32500, 32900, {}),
    ("XLA Modules", "jit_serve_decode(2)", 33000, 43000, {}),
    ("XLA Ops", "%fusion.7", 33000, 36000,
     {"tf_op": f"{DEC}/moe/experts/gather"}),
    ("XLA Ops", "%ragged-dot-none.3", 36000, 43000, RAGGED),
]


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    return write_trace(tmp_path_factory.mktemp("t") / "moe.xplane.pb",
                       EVENTS)


def context(path, arch=ARCH):
    return RunContext({"conf": FILE, "arch": arch}, PEAK, path)


def test_unscoped_kernels_count_with_their_layer(xplane):
    sp = hs.load(xplane)
    prefill = sp.modules[0]
    assert layer_ops.layer_ns(sp, prefill, "moe") == 1000 + 3000
    assert layer_ops.layer_ns(sp, prefill, "moe", ("ragged-dot",)) == \
        1000 + 9000 + 3000
    # an op the module does not name stays out (the decode's copy)
    assert layer_ops.layer_ns(sp, sp.modules[1], "moe",
                              ("ragged-dot",)) == 1000 + 5000
    assert layer_ops.unscoped(ARCH, "moe") == ("ragged-dot",)
    assert layer_ops.unscoped(types.SimpleNamespace(), "moe") == ()


def test_decode_moe_ms_on_hand_computed_values(xplane):
    # 1000 + 5000 in the first step, 3000 + 7000 in the second
    assert reader("decode_moe_ms").read(context(xplane)) == \
        pytest.approx(8000 / 1e6)
    # a module that names no unscoped op reads the scope alone
    bare = types.SimpleNamespace(prefill_moe=ARCH.prefill_moe)
    assert reader("decode_moe_ms").read(context(xplane, bare)) == \
        pytest.approx(2000 / 1e6)


def test_moe_roofline_on_hand_computed_values(xplane):
    least = work.roofline_s(*ARCH.prefill_moe(FILE, 512), PEAK)[0]
    want = 100.0 * least / (13000 / 1e9)
    assert reader("moe_roofline").read(context(xplane)) == \
        pytest.approx(want)


def test_moe_readers_read_nothing_without_the_scope(tmp_path):
    """A dense model's trace, or an untraced run: nothing, no raise."""
    dense = [e for e in EVENTS if "moe" not in e[4].get("tf_op", "")
             and "ragged" not in e[1]]
    path = write_trace(tmp_path / "dense.xplane.pb", dense)
    assert reader("decode_moe_ms").read(context(path)) is None
    assert reader("decode_moe_ms").read(context(None)) is None
    assert reader("moe_roofline").read(
        context(path, types.SimpleNamespace())) is None
