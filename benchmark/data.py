"""The cell's input: seeded TPC-H tables written as Parquet, once per seed.

The generators (``tables/``), the files, their layout and everything
computed from them are the benchmark's. Data lives under
``benchmark/.data/seed<n>/<table>/`` inside the checkout (git-ignored), one
seed at a time: a run deletes what another seed left, so a dozen seeds at
~0.15 GB each never sit in the tree. Only the tables the cell's traffic reads
are written; a table that is there for the seed is reused.

The tables are generated and written by a child process (this module run as
a script; it never imports jax, so it needs no chip). The timed process then
never holds the generator's allocator state, and reads the same whether it
made its data or found it: PERF.md section 6 has the readings.
"""
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
DATA_DIR = os.path.join(HERE, ".data")
DONE = "_done"    # written last: a table without it is written again


def data_root(seed: int, scale: float) -> str:
    tag = f"seed{seed}" if scale == 1 else f"seed{seed}_sf{scale:g}"
    return os.path.join(DATA_DIR, tag)


def write_table(name: str, root: str, files: int, scale: float, seed: int):
    """Generate table ``name`` and write it as ``files`` Parquet files."""
    import pyarrow.parquet as pq

    from benchmark import tables
    table = tables.generate(name, scale, seed)
    d = os.path.join(root, name)
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d)
    per = -(-table.num_rows // files)
    for i in range(files):
        path = os.path.join(d, f"part-{i}.parquet")
        pq.write_table(table.slice(i * per, per), path)
        # write-back now, in set-up, not whenever the kernel chooses to
        # inside the window
        with open(path, "rb") as f:
            os.fsync(f.fileno())
    open(os.path.join(d, DONE), "w").close()


def ensure_data(config: dict, tables, seed: int, scale=None) -> str:
    """The configuration's ``tables`` for ``seed``, written where they are
    not there yet; returns the directory, one sub-directory per table,
    ``files_per_table`` files each. ``scale`` overrides the configuration's
    scale factor (CPU rehearsals and tests)."""
    scale = config["scale_factor"] if scale is None else scale
    root = data_root(seed, scale)
    if os.path.isdir(DATA_DIR):
        for other in os.listdir(DATA_DIR):
            if other != os.path.basename(root):
                shutil.rmtree(os.path.join(DATA_DIR, other))
    missing = [t for t in tables
               if not os.path.exists(os.path.join(root, t, DONE))]
    if missing:
        subprocess.run(
            [sys.executable, os.path.abspath(__file__), root,
             str(config["files_per_table"]), repr(scale), str(seed)]
            + missing, check=True)
    return root


if __name__ == "__main__":
    sys.path[0] = os.path.dirname(HERE)   # the checkout, not benchmark/
    root_, files_, scale_, seed_ = sys.argv[1:5]
    for name_ in sys.argv[5:]:
        write_table(name_, root_, int(files_), float(scale_), int(seed_))
