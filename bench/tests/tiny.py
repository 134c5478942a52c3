"""A cell at a size the CPU runs in seconds, for the tests: the qwen1.5
configuration and the closed-loop traffic mix with every size cut, under
every metric of ``BENCHMARK.json``.  Widths stay multiples of
the 128-wide SME tile, so every projection is packed and served through
the SME path."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from spec import BENCH as _B, REPO, Cell, read_json  # noqa: E402

TINY = dict(hidden_size=128, num_attention_heads=2, num_key_value_heads=2,
            intermediate_size=256, num_hidden_layers=2, vocab_size=4096)
#: the tiny cell's limit on the mean logit gap, set from tiny readings
#: on the CPU (seeds 1-12): sound runs read 0 to 9.7e-7, the float8
#: control 3.8e-5 to 1.8e-4
TINY_LOGIT_GAP_MEAN = 6e-6


def tiny_cell(traffic: str = "closed-decode",
              logit_gap_mean: float = TINY_LOGIT_GAP_MEAN) -> Cell:
    spec = read_json(REPO / "BENCHMARK.json")
    cfg = read_json(_B / "configs" / "qwen1.5-0.5b.sme-v2.json")
    cfg.update(TINY)
    cfg["limits"] = {"logit_gap_mean": logit_gap_mean}
    tr = read_json(_B / "traffic" / f"{traffic}.json")
    tr.update(slots=3, s_max=64, check_requests=12, clients=3, pool=24,
              block=8, preroll_steps=4)
    tr["prompt_tokens"] = dict(median=10, sigma=0.4, min=8, max=16)
    tr["output_tokens"] = dict(median=20, sigma=0.5, min=10, max=40)
    return Cell(f"tiny-{traffic}", "tiny", cfg, traffic, tr, 1,
                spec["end_to_end"], spec["per_layer"])
