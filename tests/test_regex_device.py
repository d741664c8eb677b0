import numpy as np
import pytest




def test_regexp_replace_group_refs(session):
    """$n group references run on device over the group-plan subset
    (reference: GpuRegExpReplace, stringFunctions.scala:895)."""
    import re as _re

    import pyarrow as pa

    from spark_rapids_tpu.expr.functions import col, regexp_replace
    data = ["abc-123 def-456", "x-1", "nope", "", "zz-99 a-1 b-22",
            "tail abc-7", "-", "ab-12cd-34"]
    df = session.create_dataframe(pa.table({"s": data}))
    cases = [(r"([a-z]+)-(\d+)", "$2:$1"),
             (r"([a-z]+)-(\d+)", "[$0]"),
             (r"([a-z]+)-(\d+)", "$1"),
             (r"([a-z]+)-(\d+)", r"\$$2"),
             (r"([a-z]+)-(\d+)", "<$1-$2>")]
    for pat, repl in cases:
        q = df.select(regexp_replace(col("s"), pat, repl).alias("r"))
        dev = q.collect(device=True).column("r").to_pylist()
        cpu = q.collect(device=False).column("r").to_pylist()
        pyrep = _re.sub(r"\$(\d+)", r"\\g<\1>",
                        repl.replace("\\$", "\0")).replace("\0", "$")
        exp = [_re.sub(pat, pyrep, s) for s in data]
        assert dev == exp, (pat, repl, dev, exp)
        assert cpu == exp, (pat, repl)
    # alternation pattern + refs: falls back, still correct
    q = df.select(regexp_replace(col("s"), r"(ab|zz)-(\d+)", "$2").alias("r"))
    assert q.collect(device=True).column("r").to_pylist() \
        == q.collect(device=False).column("r").to_pylist()


# ---- Q13's predicate: o_comment [NOT] LIKE '%special%requests%' ----------------
Q13_PATTERN = "%special%requests%"
#: one string a case; each frame also holds a plain comment and a null
Q13_CASES = {
    "empty": "",
    "words-apart": "carefully special foxes sleep; regular requests nag",
    "back-to-back": "specialrequests",
    "requests-before-special": "furiously requests wake the special deposits",
    "special-alone": "blithely special packages",
    "77-bytes-match-at-the-end": ("slyly special " + "x" * 55 + "requests"),
    "77-bytes-no-match": ("slyly special " + "x" * 55 + "request."),
    "null": None,
}


def _q13_frame(session, case):
    import pyarrow as pa
    data = [Q13_CASES[case], "regular deposits haggle", None]
    return session.create_dataframe(pa.table({"s": pa.array(data, pa.string())}))


def _q13_expected(values):
    import re as _re
    rx = _re.compile("special.*requests", _re.DOTALL)
    return [None if s is None else rx.search(s) is not None for s in values]


def test_the_q13_cases_are_what_they_say():
    assert len(Q13_CASES["77-bytes-match-at-the-end"].encode()) == 77
    assert len(Q13_CASES["77-bytes-no-match"].encode()) == 77
    assert _q13_expected(list(Q13_CASES.values())) == [
        False, True, True, False, False, True, False, None]


def test_the_q13_pattern_takes_the_nfa_not_a_simple_search():
    from spark_rapids_tpu.expr.functions import col
    like = col("s").like(Q13_PATTERN).expr
    assert like.simple_kind() is None
    assert like.to_regex() == "^.*special.*requests.*$"
    assert col("s").like("%special%").expr.simple_kind() \
        == ("contains", "special")


@pytest.mark.parametrize("negate", [False, True], ids=["like", "not-like"])
@pytest.mark.parametrize("case", list(Q13_CASES))
def test_q13_like_on_the_device_against_python_re(session, case, negate):
    """The device NFA's answer, row for row, against ``re.search`` (LIKE's
    leading and trailing ``%``), with SQL's nulls: a null comment is
    neither LIKE nor NOT LIKE, so a filter drops it either way."""
    from spark_rapids_tpu.expr.functions import col
    df = _q13_frame(session, case)
    pred = col("s").like(Q13_PATTERN)
    if negate:
        pred = ~pred
    values = df.collect(device=False).column("s").to_pylist()
    want = [None if m is None else (m != negate)
            for m in _q13_expected(values)]
    q = df.select(pred.alias("m"))
    assert q.collect(device=True).column("m").to_pylist() == want
    assert q.collect(device=False).column("m").to_pylist() == want
    kept = df.filter(pred).collect(device=True).column("s").to_pylist()
    assert kept == [s for s, m in zip(values, want) if m]


def _lowered_text(pattern):
    """The text of the program that evaluates ``s LIKE pattern`` over a
    device batch, with each op's scope."""
    import jax
    import pyarrow as pa

    from spark_rapids_tpu.columnar.device import DeviceTable
    from spark_rapids_tpu.columnar.host import HostTable
    from spark_rapids_tpu.expr.base import EvalContext
    from spark_rapids_tpu.expr.functions import col
    table = DeviceTable.from_host(HostTable.from_arrow(
        pa.table({"s": ["special requests", "none"]})), min_bucket=8)
    like = col("s").like(pattern).expr

    def run(t):
        return like.eval(EvalContext.for_device(t)).values
    return jax.jit(run).lower(table).as_text(debug_info=True)


@pytest.mark.parametrize("pattern,scope,other", [
    (Q13_PATTERN, "like_nfa", "like_search"),
    ("%special%", "like_search", "like_nfa"),
], ids=["nfa", "simple-search"])
def test_like_ops_carry_the_name_of_their_path(pattern, scope, other):
    text = _lowered_text(pattern)
    assert scope in text and other not in text


# ---- the NFA's scan against Python's re, pattern by pattern ----------------------
NFA_PATTERNS = ["^.*special.*requests.*$", "^a.c$", "^.*a.*b$", "a.c", "^[A-Z]",
                "ing$", "[0-9]+|[a-z]{3}", "Spa?rk", "x.", "^.$", "^[^a]+$",
                "a.$", "ab*c", "(ab|cd)+e", "^$", "a?", "\\d{2,3}", "[^x-z]q",
                "b$", "^(ab)*$", "[a-c]{2}[^b]"]


def _nfa_subjects():
    rng = np.random.default_rng(41)
    alphabet = list("abcdeqxyzrs ABCZ0129") + [
        "special", "requests", "ing", "Spark", "é", "été"]
    subjects = ["", "a", "é", "abc", "special requests",
                "requestsspecial", "specialrequests"]
    subjects += ["".join(rng.choice(alphabet, rng.integers(0, 12)))
                 for _ in range(300)]
    return subjects


@pytest.mark.parametrize("pattern", NFA_PATTERNS)
def test_the_nfa_scan_finds_what_python_re_finds(pattern):
    """``DeviceNfa.matches`` (find() semantics, one character a step, a
    multi-byte character stepped once) row for row against ``re.search``
    over 307 subjects, two-byte characters among them, at a width bucket
    wider than the longest."""
    import re as _re

    import jax.numpy as jnp

    from spark_rapids_tpu.expr.regex import compile_device_nfa
    nfa = compile_device_nfa(pattern)
    assert nfa is not None
    subjects = _nfa_subjects()
    raw = [s.encode() for s in subjects]
    values = np.zeros((len(raw), 128), np.uint8)
    for i, b in enumerate(raw):
        values[i, :len(b)] = np.frombuffer(b, np.uint8)

    class Ctx:
        xp = jnp

    class Col:
        pass
    c = Col()
    c.values = jnp.asarray(values)
    c.lengths = jnp.asarray([len(b) for b in raw], dtype=jnp.int32)
    got = np.asarray(nfa.matches(Ctx, c)).tolist()
    rx = _re.compile(pattern)
    assert got == [rx.search(s) is not None for s in subjects]
