"""Mean host time of one EASY backfill pass (``EasyBackfill.backfill``
with the reservation ledger), from the benchmark's wrapper."""


def read(ctx):
    return ctx.spans.get("policy_pass").mean_ms()
