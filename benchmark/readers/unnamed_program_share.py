"""Share of the device's module seconds spent in programs without one of
the program's fixed names (``jit_srt_*``), in %: eager ``jnp`` operations
dispatched one by one between the named programs
(``jit_convert_element_type``, ``jit_dynamic_slice``, ...), and any program
a refactor left unnamed. A program without the names reads 100."""


def read(run, prefix="jit_srt_"):
    modules = run["trace"].get("module_s")
    total = sum(s for _, s in modules) if modules else 0.0
    if not total:
        return None
    named = sum(s for name, s in modules if name.startswith(prefix))
    return 100.0 * (1.0 - named / total)
