"""What is one architecture's alone, one module per ``model_type`` of a
configuration file: how its configuration maps onto ``serving.GPTConfig``,
its weight table, its plain reference and tolerance, and the bytes and
operations its per-layer metrics divide by. ``runners/serve_arch.py`` and
the readers look a module up by that name, so the next architecture adds
a configuration file, a reference and one module here."""
import importlib


def engine_args(engine: dict) -> dict:
    """``DecodeModel`` keywords from a traffic file's ``engine`` settings."""
    return dict(max_batch=int(engine["max_batch"]), n_blocks=int(engine["n_blocks"]),
                block_size=int(engine["block_size"]),
                prefill_buckets=[int(b) for b in engine["prefill_buckets"]])


def of(config: dict):
    """The module of a configuration's ``model_type``."""
    return importlib.import_module(f"{__name__}.{config['model_type']}")
