"""The traffic generator and the files BENCHMARK.json names, on the CPU,
without the program."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import harness, traffic

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())

MIX = {"loop": "batch", "slots": 3,
       "prompt": {"dist": "uniform", "min": 2, "max": 20},
       "output": {"dist": "uniform", "min": 1, "max": 4}}


def test_same_seed_same_requests():
    mix = MIX
    a, b = traffic.Traffic(mix, 2 ** 40 + 1, 100), \
        traffic.Traffic(mix, 2 ** 40 + 1, 100)
    c = traffic.Traffic(mix, 2 ** 40 + 2, 100)
    ra = [a.request(i) for i in range(300)]
    rb = [b.request(i) for i in range(300)]
    rc = [c.request(i) for i in range(300)]
    for x, y in zip(ra, rb):
        assert np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
    assert any(not np.array_equal(x.prompt, z.prompt) for x, z in zip(ra, rc))
    # another seed gets the same sizes in another order, block by block
    n = traffic.BLOCK
    assert sorted(len(r.prompt) for r in ra[:n]) == \
        sorted(len(r.prompt) for r in rc[:n])
    assert sorted(r.max_new for r in ra[:n]) == \
        sorted(r.max_new for r in rc[:n])


def test_lengths_within_clips_and_median():
    t = traffic.Traffic(MIX, 5, 100)
    lens = np.array([len(t.request(i).prompt) for i in range(traffic.BLOCK)])
    assert lens.min() >= 2 and lens.max() <= 20
    assert np.median(lens) == 11


def test_unknown_loop_and_distribution_refused():
    with pytest.raises(ValueError, match="unknown loop"):
        traffic.Traffic(dict(MIX, loop="open"), 1, 100)
    with pytest.raises(ValueError, match="unknown length distribution"):
        traffic.Traffic(dict(MIX, prompt={"dist": "lognormal", "min": 1,
                                          "max": 9}), 1, 100).request(0)


@pytest.mark.parametrize("steps, want", [
    (16, [(0, 0), (2, 2), (4, 4), (6, 6), (9, 9), (11, 11), (13, 13),
          (15, 15)]),
    (64, [(0, 0), (9, 9), (18, 18), (27, 27), (36, 36), (45, 45), (54, 54),
          (63, 63)]),
    (6, [(0, 5)]),
    (2, [(0, 1)]),
    (1, [(0, 0)]),
])
def test_traced_stretches_span_the_batch(steps, want):
    assert harness.trace_stretches(steps) == want


def test_files_named_by_benchmark_load():
    from bench import configfile

    for c in BENCH["configs"]:
        config = json.loads((ROOT / c["file"]).read_text())
        assert config["name"] == c["name"]
        assert config["reduced"] == c["reduced"]
        assert config["program_keys"] and configfile.packed_ops(config)
        for key, names in (("reference", ("logits", "Q_BLOCK")),
                           ("weights", ("make_weights",)),
                           ("work", ("dense_calls", "decode_ops",
                                     "prompt_ops", "head_ops"))):
            mod = configfile.module(config, key)
            assert all(hasattr(mod, n) for n in names), (key, names)
    for w in BENCH["workloads"]:
        mix = json.loads((ROOT / "bench" / "traffic" /
                          f"{w['traffic']}.json").read_text())
        traffic.Traffic(mix, 0, 100).request(0)
    for m in BENCH["per_layer"]:
        assert callable(harness._load_reader(m["name"]))


@pytest.mark.parametrize("path", sorted((ROOT / "bench" / "traffic")
                                        .glob("*.json")), ids=lambda p: p.stem)
def test_every_mix_file_generates(path):
    mix = json.loads(path.read_text())
    t = traffic.Traffic(mix, 2 ** 33 + 9, 151936)
    r = t.request(0)
    p_max, g_max = traffic.max_lengths(mix)
    assert 1 <= len(r.prompt) <= p_max and 1 <= r.max_new <= g_max
