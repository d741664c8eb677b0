"""The benchmark's own TPC-H table generators, one module per table, found
by the table's name: ``tables/<table>.py`` has ``generate(sf, seed)``, which
returns every column of the table (spec cl. 1.4) as a pyarrow table.

They follow the value domains of the program's ``tools/tpch.py`` (numpy,
seeded, dbgen-flavoured; money and quantity as float64; row counts by the
spec's scale factors with 6,000,000 lineitem rows a unit) and are kept here
so that no later PR can change the benchmark's data by changing the program.
String columns are drawn as indices into their value lists and built as
Arrow dictionary arrays, which is what makes SF 1 take seconds. Every seed
gives the same row counts and the same domains, so every seed is the same
work. Nothing here imports jax: ``data.py`` runs it in a child process.
"""
import importlib

import numpy as np
import pyarrow as pa

EPOCH_1992 = 8035    # days from the unix epoch to 1992-01-01
DATE_RANGE = 2557    # ~7 years of ship dates
CURRENT_DATE = EPOCH_1992 + 1263   # 1995-06-17, dbgen's CURRENTDATE (cl. 4.2.3)

FILLER = [
    "carefully", "quickly", "furiously", "slyly", "blithely", "ironic",
    "regular", "final", "bold", "pending", "express", "silent", "even",
    "unusual", "daring", "idle", "busy", "brave", "quiet", "ruthless",
    "deposits", "requests", "packages", "accounts", "instructions",
    "theodolites", "foxes", "pinto", "beans", "dependencies", "platelets",
    "excuses", "ideas", "sheaves", "asymptotes", "dugouts", "sauternes",
    "warthogs", "courts"]


def strings(values, idx) -> pa.Array:
    """``values[idx]`` as a plain string array."""
    return pa.DictionaryArray.from_arrays(
        pa.array(np.asarray(idx, dtype=np.int32)),
        pa.array(list(values))).cast(pa.string())


def choice(rng, values, n) -> pa.Array:
    """n draws from a short list of strings."""
    return strings(values, rng.integers(0, len(values), size=n))


def sentences(rng, n, words, width) -> pa.Array:
    """Comment strings of at most ``width`` characters, drawn from a pool of
    128 sentences of ``words`` filler words."""
    pool = [" ".join(rng.choice(FILLER, words))[:width] for _ in range(128)]
    return strings(pool, rng.integers(0, len(pool), size=n))


def dates(days) -> pa.Array:
    return pa.array(days.astype(np.int32), type=pa.int32()).cast(pa.date32())


def generate(table: str, sf: float, seed: int) -> pa.Table:
    return importlib.import_module(f"{__name__}.{table}").generate(sf, seed)
