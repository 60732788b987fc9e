"""The reader of ``record_shuffle_ms`` on hand-made traces.  Run:
``python -m pytest benchmark/tests -q``."""

import os

import pytest

from benchmark import run, trace

MS = 1_000_000  # ns
READER = run.load_module(os.path.join(run.HERE, "metrics", "record_shuffle_ms.py"))


def _ms(events):
    return [[*e[:-2], e[-2] * MS, e[-1] * MS] for e in events]


def _device(i, module, ops):
    return {"name": f"/device:TPU:{i}", "lines": [
        {"name": "XLA Modules", "events": _ms([[module, 0, 100]])},
        {"name": "XLA Ops", "events": _ms(ops)}]}


def _run(devices, jobs=1):
    spans = [["bench:sort_kv_host", 100 * j // jobs, 100 // jobs] for j in range(jobs)]
    s = trace.reduce({"planes": [
        {"name": "/host:CPU", "lines": [{"name": "spans", "events": _ms(spans)}]},
        *devices]})
    return run.Run([], 0.0, s, 1 << 22, len(devices), 100, "TPU v5 lite")


#: One chip's record program across chips: local sort, the splitters'
#: all-gather, the pack gathers, the all-to-alls, the combine and its gather.
FOUR_CHIP_OPS = [
    ["sort.1", "sort", 0, 20], ["fusion", "fusion", 20, 10],
    ["all-gather.2", "all-gather", 30, 1], ["sort.3", "sort", 31, 1],
    ["fusion.3", "fusion", 32, 8], ["all-to-all.4", "all-to-all", 40, 3],
    ["all-to-all.5", "all-to-all", 43, 5], ["sort.6", "sort", 48, 12],
    ["fusion.7", "fusion", 60, 9]]
KV2 = "jit__sample_sort_kv2_shard(5)"


def test_counts_the_ops_after_the_first_collective_and_not_collectives():
    r = _run([_device(0, KV2, FOUR_CHIP_OPS)])
    assert READER.read(r) == pytest.approx(1 + 8 + 12 + 9)


def test_takes_the_busiest_chip_per_job():
    slower = [op[:3] + [op[3] * 2] if op[0] == "fusion.3" else op for op in FOUR_CHIP_OPS]
    slower = [op if op[2] < 40 else [*op[:2], op[2] + 8, op[3]] for op in slower]
    r = _run([_device(0, KV2, FOUR_CHIP_OPS), _device(1, KV2, slower)], jobs=2)
    assert READER.read(r) == pytest.approx((1 + 16 + 12 + 9) / 2)


def test_a_one_chip_record_program_reads_none():
    one_chip = [["sort.1", "sort", 0, 20], ["fusion", "fusion", 20, 10],
                ["copy.2", "copy", 30, 2]]
    assert READER.read(_run([_device(0, "jit__sample_sort_kv2_shard(1)", one_chip)])) is None


def test_a_keys_program_and_a_run_without_trace_read_none():
    assert READER.read(_run([_device(0, "jit__sample_sort_shard(2)", FOUR_CHIP_OPS)])) is None
    assert READER.read(run.Run([], 0.0, None, 1, 4, 100, "TPU v5 lite")) is None
