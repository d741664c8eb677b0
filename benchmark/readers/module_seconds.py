"""Device seconds of the programs whose XLA module name starts with a
prefix, optionally per traced query. The program gives its device programs
fixed names (``spark_rapids_tpu/utils/compile_cache.py`` ``PROGRAM_NAMES``:
module ``jit_srt_<name>``), so a prefix selects a layer's programs whatever
their shapes: ``jit_srt_pq_decode`` is both Parquet decoders. Module seconds
are those of the busiest device inside the traced window
(``reduce_trace.py`` ``module_s``). No module under the prefix: nothing to
read."""


def read(run, prefix, per_query=True):
    matched = [s for name, s in run["trace"].get("module_s", ())
               if name.startswith(prefix)]
    if not matched:
        return None
    total = sum(matched)
    return total / run["trace"]["queries"] if per_query else total
