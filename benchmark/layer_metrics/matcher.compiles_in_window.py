"""Programs traced and compiled inside the window; should read 0 (every
shape is warmed up in set-up)."""

SPEC = {"layer": "device matcher ops/partitioned.py", "unit": "count",
        "source": "program_counter", "moves": "deliveries_per_s"}


def read(run: dict):
    return (run["after"]["device"]["compile"]["traces"]
            - run["before"]["device"]["compile"]["traces"])
