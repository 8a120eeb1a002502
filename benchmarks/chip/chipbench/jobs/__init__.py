"""One module per job a traffic file can name (its ``job`` key).

Each defines ``run(run: harness.Run) -> harness.Outcome``.
"""
