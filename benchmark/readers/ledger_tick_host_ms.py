"""Host work of one decode tick, in milliseconds, from the engine ledger's
counters over every tick since the runner's ``ledger.reset()`` (read
after the run; the totals outlive ``engine.stop()``):
(``tick_wall_s`` - ``tick_sync_s``) / ``decode_ticks``. ``tick_wall_s``
sums the ``engine/decode_tick`` spans and ``tick_sync_s`` the
``tick/device_sync`` spans inside them, so the difference is the time a
tick spends NOT blocked on the device: growing blocks, building and
putting inputs, the enqueue, the bookkeeping. The report gets the three
means a tick (``tick_ms``). None where the ledger has no such counters."""


def read(ctx, args):
    from paddle_tpu.serving import ledger

    doc = ledger.totals()
    n = doc.get("decode_ticks")
    if not n or "tick_wall_s" not in doc or "tick_sync_s" not in doc:
        return None
    wall, sync = 1e3 * doc["tick_wall_s"] / n, 1e3 * doc["tick_sync_s"] / n
    ctx.results["tick_ms"] = {"decode_ticks": n, "wall": wall, "device_sync": sync, "host": wall - sync}
    return wall - sync
