"""Runners, one module per traffic ``kind``: ``run(ctx)`` fills ctx.results."""
