"""CPU test settings for configurations added after the correctness tests.

``test_bench_correctness.SIZES`` names the small volume each
configuration's control test draws; a configuration it does not name
takes the same 16x16x16 volume as the others."""

import os

from bench import harness, test_bench_correctness

for _cfg in harness.load_json(os.path.join(harness.ROOT,
                                           "BENCHMARK.json"))["configs"]:
    test_bench_correctness.SIZES.setdefault(_cfg["name"], (16, 16, 16))
