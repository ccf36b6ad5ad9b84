"""§Perf hillclimb driver: three chosen cells, hypothesis->change->measure.

Cells (from the baseline table):
  1. moonlight_16b_a3b x train_4k     — dense MoE dispatch computes all
     64 experts/token.
  2. internvl2_2b x decode_32k        — most collective-bound: KV-repeat
     forces an involuntary SPMD resharding of the cache.
  3. qwen3_32b x decode_32k           — most representative of the paper's
     technique: the serving data plane the Planter gate fuses into, and
     the int8-KV lever mirrors the paper's action-bits quantization.
"""
import repro.launch.dryrun as DR  # noqa: F401  (XLA flags first)
import json
import sys

from benchmarks.roofline import measure_cell

RUNS = [
    # (cell, label, overrides)
    (("moonlight_16b_a3b", "train_4k"), "baseline(dense-moe)", {}),
    (("moonlight_16b_a3b", "train_4k"), "sparse-dispatch",
     {"moe_impl": "sparse"}),
    (("internvl2_2b", "decode_32k"), "baseline(repeat-gqa)", {}),
    (("internvl2_2b", "decode_32k"), "grouped-gqa",
     {"gqa_impl": "grouped"}),
    (("internvl2_2b", "decode_32k"), "grouped+int8kv",
     {"gqa_impl": "grouped", "kv_dtype": "int8"}),
    (("qwen3_32b", "decode_32k"), "baseline(repeat-gqa)", {}),
    (("qwen3_32b", "decode_32k"), "grouped-gqa", {"gqa_impl": "grouped"}),
    (("qwen3_32b", "decode_32k"), "grouped+int8kv",
     {"gqa_impl": "grouped", "kv_dtype": "int8"}),
]


def main():
    results = []
    for (arch, shape), label, ov in RUNS:
        try:
            r = measure_cell(arch, shape, overrides=ov, verbose=False)
            r["label"] = label
            results.append(r)
            print(f"{arch:22s} {shape:11s} {label:22s} "
                  f"C={r['compute_s']*1e3:9.2f}ms "
                  f"M={r['memory_s']*1e3:9.2f}ms "
                  f"N={r['collective_s']*1e3:9.2f}ms "
                  f"dom={r['dominant'][:4]} "
                  f"bound={r['step_s_bound']*1e3:9.2f}ms")
        except Exception as e:
            print(f"FAIL {arch} {shape} {label}: {e}", file=sys.stderr)
            results.append({"arch": arch, "shape": shape, "label": label,
                            "error": str(e)[:300]})
    with open("/root/repo/hillclimb_results.json", "w") as f:
        json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
