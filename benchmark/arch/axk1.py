"""A.X-K1 (``model_type`` "axk1") for the benchmark: not built yet."""
LOGIT_TOL = 0.0
N_CHECKED = 4


def gpt_config(c: dict, engine: dict) -> dict:
    raise SystemExit("axk1: not built yet")
