"""Set-up: the CLI's own start (``e2e_trainer.main``) — spans
``cli_config`` (arguments, YAML, schema), ``data_load`` (datasets) and
``server_build`` (engine, ``init_state``, server construction)."""

UNIT = "s"
NAMES = ("cli_config", "data_load", "server_build")


def read(ctx):
    spans = [s for s in ctx["spans"] if s["name"] in NAMES]
    return sum(s["dur_s"] for s in spans) if spans else None
