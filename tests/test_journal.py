"""The journal contract (paddle_tpu/journal.py), held once for all five
ledgers, in the cases their own test files do not cover: a rank set
after import re-anchors the resume, disable_persistence stops every
write (the one at exit included), a torn or alien file is an absent one,
one exit hook flushes each configured journal once, and a journal the
PARENT commit wrote resumes and re-flushes with the same keys.

The fixtures under tests/data/journals/ were written by the parent
commit's own flush (PR 27's tree, before journal.py existed) with
`PYTHONPATH=<parent tree> python tests/test_journal.py <dir>`: the
`__main__` block below records the same two steps / two ticks the tests
record.
"""
import ast
import inspect
import json
import os
import shutil
import subprocess
import sys

import pytest

from paddle_tpu import commswatch, dynamics, goodput, memwatch, monitor
from paddle_tpu.serving import ledger as serving

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_FIXTURES = os.path.join(_REPO, "tests", "data", "journals")


def _goodput_step(i):
    goodput.add("device_compute", 0.6)
    goodput.add("input_wait", 0.1)
    goodput.end_step(1.0, samples=8, step=i)


def _memwatch_step(i):
    memwatch.sample(stats={"bytes_in_use": 500 + 400 * i,
                           "peak_bytes_in_use": 500 + 400 * i,
                           "bytes_limit": 16_000_000_000,
                           "source": "synthetic"})
    memwatch.end_step(step=i)


def _dynamics_step(i):
    dynamics.feed(loss=2.0 - 0.2 * i, grad_norm=1.0, lr=0.1)
    dynamics.end_step(step=i)


def _commswatch_step(i):
    commswatch.configure_attribution({"dp": 1 << 20})
    commswatch.record_bandwidth("all_reduce", "dp", 1 << 20, 4, 0.001)
    commswatch.end_step(collective_seconds=0.002, step=i)


def _serving_tick(i):
    serving.add("decode_compute", 0.02)
    serving.add_slot_seconds(0.04)
    serving.note_decode_tick(0.025, 0.015)
    serving.end_tick(0.03, decoded_tokens=2, active=2, max_batch=4,
                     kv_used=3, kv_total=16, queued=0)
    serving.record_request(outcome="ok", ttft_s=0.01, latency_s=0.06,
                           prompt_tokens=3, output_tokens=2,
                           span_seconds=0.04)


# ledger module, one closed step or tick, the key that counts them, and
# configure()'s name for the flush cadence
_LEDGERS = {
    "goodput": (goodput, _goodput_step, "steps", "flush_steps"),
    "memwatch": (memwatch, _memwatch_step, "steps", "flush_steps"),
    "dynamics": (dynamics, _dynamics_step, "steps", "flush_steps"),
    "commswatch": (commswatch, _commswatch_step, "steps", "flush_steps"),
    "serving": (serving, _serving_tick, "ticks", "flush_ticks"),
}


def _reset_all():
    for mod, _, _, _ in _LEDGERS.values():
        mod.disable_persistence()
        mod.reset()


@pytest.fixture(autouse=True)
def _fresh():
    monitor.enable(True)
    _reset_all()
    yield
    monitor.set_trainer_rank(0)
    _reset_all()


def _ledgers(*names):
    return pytest.mark.parametrize("name", names or list(_LEDGERS))


@_ledgers("memwatch", "dynamics", "commswatch", "serving")
def test_rank_change_reanchors_resume(name, tmp_path):
    """A rank set after import (profiler.set_rank) must not keep another
    rank's resumed journal as this rank's base, and the next flush goes
    to the new rank's file."""
    mod, step, count, _ = _LEDGERS[name]
    mod.configure(dir=str(tmp_path))
    step(0)
    rank0 = mod.flush()
    mod.reset()
    mod.configure(dir=str(tmp_path))  # pristine: resumes rank 0's journal
    assert mod.totals()[count] == 1
    monitor.set_trainer_rank(3)  # rank 3 has no journal
    assert mod.totals()[count] == 0
    step(0)
    path = mod.flush()
    assert path != rank0 and path == mod.journal_path()
    doc = mod.load_journal(path)
    assert doc["rank"] == 3 and doc[count] == 1
    # back to rank 0 while pristine: its journal is the base again
    mod.reset()
    monitor.set_trainer_rank(0)
    assert mod.totals()[count] == 1


@_ledgers("memwatch", "dynamics", "commswatch", "serving")
def test_disable_persistence_stops_writes(name, tmp_path):
    mod, step, _, every = _LEDGERS[name]
    mod.configure(dir=str(tmp_path), **{every: 1})
    mod.disable_persistence()
    step(0)
    assert mod.flush() is None
    assert list(tmp_path.iterdir()) == []


@_ledgers()
def test_one_exit_hook_flushes_each_configured_journal_once(
        name, tmp_path, monkeypatch):
    from paddle_tpu import journal

    mod, step, count, _ = _LEDGERS[name]
    registered = []
    monkeypatch.setattr("atexit.register",
                        lambda fn, *a, **kw: registered.append(fn))
    for _ in range(3):
        mod.configure(dir=str(tmp_path))
    assert registered == []  # the module's one hook is all there is
    step(0)
    writes = []
    real = monitor.atomic_write_text
    monkeypatch.setattr(
        monitor, "atomic_write_text",
        lambda path, text: (writes.append(path), real(path, text))[1])
    journal._flush_at_exit()
    assert writes == [mod.journal_path()]
    assert mod.load_journal(writes[0])[count] == 1
    # a supervisor that shed its persistence leaves the file alone
    os.remove(writes[0])
    journal.disable_persistence()
    journal._flush_at_exit()
    assert list(tmp_path.iterdir()) == []


@_ledgers("memwatch", "commswatch", "serving")
@pytest.mark.parametrize("text", ['{"schema": "something/else"}',
                                  '{"schema": "paddle_tpu.'],
                         ids=["alien", "torn"])
def test_alien_or_torn_file_is_absent(name, text, tmp_path):
    mod, step, count, _ = _LEDGERS[name]
    path = mod.journal_path(str(tmp_path))
    with open(path, "w") as f:
        f.write(text)
    with pytest.raises(ValueError):
        mod.load_journal(path)
    assert mod.load_journals(str(tmp_path)) is None
    mod.configure(dir=str(tmp_path))  # resumes nothing
    assert not mod.totals().get("resumed_from_journal")
    step(0)
    assert mod.load_journal(mod.flush())[count] == 1


def _keys(doc):
    """The key structure of a journal document, values dropped."""
    if isinstance(doc, dict):
        return {k: _keys(v) for k, v in doc.items()}
    if isinstance(doc, list):
        return [_keys(doc[0])] if doc else []
    return None


# keys a ledger's journal gained since the fixtures were written (PR 27's
# tree): a file without them is still a base this tree resumes
_ADDED = {"serving": {"ticks_ahead", "pipeline_drains",
                      "state_writes", "state_pool_bytes", "attn_layers",  # these three: PR 33
                      "prefills", "prefills_ahead",  # PR 48
                      "moe_assignments_routed",  # PR 50
                      "attn_steps"}}  # PR 51


@_ledgers()
def test_parent_commit_journal_resumes_and_reflushes(name, tmp_path):
    mod, step, count, _ = _LEDGERS[name]
    added = _ADDED.get(name, set())
    fixture = os.path.join(_FIXTURES,
                           os.path.basename(mod.journal_path()))
    parent = mod.load_journal(fixture)
    assert parent["schema"] == mod.SCHEMA and parent[count] == 2
    # the same ledger state flushes to the same keys, all the way down
    step(0)
    step(1)
    ours = mod.load_journal(mod.flush(str(tmp_path / "same_state")))
    assert added <= set(ours)
    assert _keys({k: v for k, v in ours.items() if k not in added}) \
        == _keys(parent)
    if not added:
        with open(fixture) as f, open(tmp_path / "same_state") as g:
            assert len(f.read().splitlines()) == len(g.read().splitlines())
    # and the parent's file is a base this tree resumes and extends
    shutil.copy(fixture, tmp_path)
    mod.reset()
    mod.configure(dir=str(tmp_path))
    assert mod.totals()["resumed_from_journal"]
    assert mod.totals()[count] == 2
    step(2)
    again = mod.load_journal(mod.flush())
    assert again[count] == 3
    assert set(again) == set(parent) | added | {"resumed_from_journal"}


_LEDGER_MODULES = ("goodput", "memwatch", "dynamics", "commswatch",
                   "serving", "ledger")


def test_lower_layers_name_no_ledger():
    """monitor.py and journal.py import no ledger, at module level or
    inside a function, and the launcher sheds persistence through
    journal.py alone."""
    from paddle_tpu.distributed import launch

    for fname in ("monitor.py", "journal.py"):
        with open(os.path.join(_REPO, "paddle_tpu", fname)) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            names = []
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [node.module or ""] + [a.name for a in node.names]
            for n in names:
                assert not set(n.split(".")) & set(_LEDGER_MODULES), (
                    fname, node.lineno, n)
    shed = inspect.getsource(launch._shed_rank_observability)
    code = shed.split('"""')[2]
    assert not any(m in code for m in _LEDGER_MODULES), code


def test_set_trainer_rank_imports_no_ledger():
    """A process that has only monitor.py (the package's __init__, which
    imports everything, bypassed) changes its rank, its callbacks run,
    and no ledger module appears."""
    prog = f"""
import json, sys, types
pkg = types.ModuleType("paddle_tpu")
pkg.__path__ = [{os.path.join(_REPO, "paddle_tpu")!r}]
sys.modules["paddle_tpu"] = pkg
import paddle_tpu.monitor as m
seen = []
m.on_rank_change(lambda: seen.append(m.trainer_rank()))
m.set_trainer_rank(3)
m.set_trainer_rank(3)
assert seen == [3], seen
print(json.dumps(sorted(n for n in sys.modules if n.startswith("paddle_tpu."))))
"""
    out = subprocess.run([sys.executable, "-c", prog], check=True,
                         capture_output=True, text=True, timeout=60)
    assert json.loads(out.stdout) == [
        "paddle_tpu.flags", "paddle_tpu.monitor"]


if __name__ == "__main__":
    # run on the parent commit's tree: writes that tree's journals
    out_dir = sys.argv[1]
    monitor.enable(True)
    for mod, step, _, _ in _LEDGERS.values():
        _reset_all()
        step(0)
        step(1)
        mod.flush(os.path.join(out_dir,
                               os.path.basename(mod.journal_path())))
