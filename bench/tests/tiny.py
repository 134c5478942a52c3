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
#: the tiny cell's limit on the widest logit gap, set from tiny readings
#: on the CPU (seeds 1-6): sound runs read 0 to 7.1e-4, the float8
#: control 1.0e-2 to 3.0e-2
TINY_LOGIT_GAP = 2e-3


def tiny_cell(traffic: str = "closed-decode",
              logit_gap: float = TINY_LOGIT_GAP) -> Cell:
    spec = read_json(REPO / "BENCHMARK.json")
    cfg = read_json(_B / "configs" / "qwen1.5-0.5b.sme-v2.json")
    cfg.update(TINY)
    cfg["limits"] = {"logit_gap": logit_gap}
    tr = read_json(_B / "traffic" / f"{traffic}.json")
    tr.update(slots=3, s_max=64, check_requests=6, clients=3, pool=24,
              block=8, preroll_steps=4)
    tr["prompt_tokens"] = dict(median=10, sigma=0.4, min=8, max=16)
    tr["output_tokens"] = dict(median=12, sigma=0.5, min=6, max=24)
    return Cell(f"tiny-{traffic}", "tiny", cfg, traffic, tr, 1,
                spec["end_to_end"], spec["per_layer"])
