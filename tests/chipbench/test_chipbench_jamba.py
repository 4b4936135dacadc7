"""The Jamba2-Mini cell on a host without a chip, at a small size: the
clean run comes out correct with its per-layer metrics, and three
planted faults in the timed path come out not correct. The roofline's
byte count is checked against the program's own weights.

The configuration's limits are set from readings at the published
widths (PERF.md section 4). Here they are set the same way, from
readings at this size, four seeds on the CPU: with positions whose
routing lies within 0.01 left out (this size's run holds few positions,
and at 0.05 none would be left), ``logits_err`` reads 0.0078-0.0108 for
the program, 0.0154-0.0189 with its expert weights through float8 and
0.094-0.255 with the conv state reset between chunks; the limit lies
between the program's and the faults'.
"""

import dataclasses
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench.harness import run_cell  # noqa: E402
from chipbench.manifest import Manifest  # noqa: E402
from chipbench.reference import jamba as ref  # noqa: E402

CELL = "jamba2_mini.decode"
# published structure (one period, attention at 4, 8 of 16 experts held,
# top-2) at small widths; chunks of 4 put most of a prompt next to a
# chunk boundary; max_seq leaves room for every call of the window
TINY = {"hidden_size": 256, "intermediate_size": 512,
        "num_attention_heads": 8, "num_key_value_heads": 2,
        "mamba_dt_rank": 16, "mamba_d_state": 16, "vocab_size": 256,
        "serving": {"slots": 4, "prompt_min": 8, "prompt_max": 40,
                    "max_seq": 1024, "max_tokens": 1024,
                    "prefill_chunk": 4, "compare_slots": 2},
        "routing_margin": 0.01, "limits": {"logits_err": 0.013}}
SEED = 2 ** 33 + 7


def _run(trace=False, tamper=None):
    return run_cell(ROOT, CELL, SEED, 0.3, trace, require_chip=False,
                    overrides=TINY, tamper=tamper)


def test_clean_run_is_correct_and_reports_its_metrics():
    res = _run(trace=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    for name in ("tick_device_ms", "tick_host_ms", "prefill_s"):
        assert res["metrics"][name]["value"] > 0, name


def _moe_layers(system):
    blocks = system.engine.params["blocks"]
    return [b["moe"] for b in blocks if "moe" in b]


def skip_an_expert(system):
    """Expert 3 of every MoE layer gives nothing."""
    for layer in _moe_layers(system):
        layer["wo"] = layer["wo"].at[:, 3].set(0)


def experts_through_fp8(system):
    """Every held expert's matrices rounded through float8 e4m3."""
    for layer in _moe_layers(system):
        for name in ("wi", "wg", "wo"):
            w = layer[name]
            layer[name] = ref.round_fp8(w.astype(np.float32)).astype(w.dtype)


def reset_conv_between_chunks(system):
    """The prefill drops the Mamba conv inputs carried from the last
    chunk."""
    steps = system.engine.steps

    def chunk(params, tokens, one, valid):
        one = [dict(c, ssm=dict(c["ssm"], conv=c["ssm"]["conv"] * 0))
               if "ssm" in c else c for c in one]
        return steps.chunk(params, tokens, one, valid)

    system.engine.steps = dataclasses.replace(steps, chunk=chunk)


@pytest.mark.parametrize("fault", [skip_an_expert, experts_through_fp8,
                                   reset_conv_between_chunks],
                         ids=lambda f: f.__name__)
def test_planted_fault_is_not_correct(fault):
    res = _run(tamper=fault)
    assert not res["correct"], res["checks"]


def test_roofline_bytes_count_the_programs_weights():
    """With every held expert hit and no context, a tick's bytes are
    the program's weights less the embedding table, one embedding row a
    token, and each slot's state read and written; each part then adds
    what it should."""
    import jax

    from chipbench.systems import jamba as system

    cfg = Manifest(ROOT).config("jamba2_mini_ep2")
    dm = ref.dims(cfg)
    shapes = jax.eval_shape(lambda: system.program_params(0, cfg))
    total = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert total == pytest.approx(14.379e9, rel=1e-3)   # 7.19 B in bf16
    wb = ref.weight_bytes(dm)
    seq = ref.per_sequence_bytes(dm)
    assert wb["other"] + wb["experts"] + wb["embed"] == total
    assert wb["experts"] == 4 * 8 * wb["expert"]
    assert seq["kv_per_token"] == 1 * 2 * 8 * 128 * 2
    assert seq["state"] == 7 * (3 * 8192 * 2 + 8192 * 16 * 4)
    all_hit = 4 * 8
    _, base = ref.decode_work(dm, 1, 16, 0, all_hit, 0)
    assert base == total - wb["embed"] + 16 * dm.d * 2 \
        + 2 * 16 * seq["state"]
    _, more = ref.decode_work(dm, 1, 16, 1000, all_hit, 0)
    assert more - base == 1000 * seq["kv_per_token"]
    _, fewer = ref.decode_work(dm, 1, 16, 0, all_hit - 5, 0)
    assert base - fewer == 5 * wb["expert"]
    flops, nbytes = ref.decode_work(dm, 2, 32, 0, 2 * all_hit, 64)
    assert nbytes == 2 * base and flops > 0
