"""A string predicate is resolved to dictionary codes once per
dictionary state (storage/dictionary.py `Dictionary.codes_where`,
planner/bind.py `_codes_where`): the second bind of a LIKE, a string IN
or a string BETWEEN visits no dictionary value, a dictionary that grew
is walked over its new tail only, and the codes are what the full walk
gives.  On the parent every bind walked every distinct value in Python
(0.3 s a statement at 1.5 M values)."""

import pytest

import citus_tpu
from citus_tpu.planner import expr as ir
from citus_tpu.storage.dictionary import Dictionary

PREDICATES = {
    "like": "note like '%special%requests%'",
    "not_like": "note not like 'plain%'",
    "in": "note in ('plain 3', 'plain 5', 'absent')",
    "between": "note between 'plain 2' and 'plain 4'",
    "less": "note < 'plain 3'",
}


def notes(n: int, start: int = 0) -> list[str]:
    return [("very special packages; requests %d" % i) if i % 4 == 0
            else "plain %d" % i for i in range(start, start + n)]


@pytest.fixture
def sess(tmp_path):
    s = citus_tpu.connect(data_dir=str(tmp_path / "d"), n_devices=1,
                          serving_result_cache_bytes=0)
    s.execute("create table t (k bigint, note text)")
    s.create_distributed_table("t", "k", shard_count=4)
    rows = ", ".join(f"({i}, '{v}')" for i, v in enumerate(notes(40)))
    s.execute(f"insert into t (k, note) values {rows}")
    yield s
    s.close()


def walked(sess) -> tuple[int, int]:
    snap = sess.stats.counters.snapshot()
    return (snap["dict_predicate_walks_total"],
            snap["dict_predicate_values_total"])


def expected(values: list[str], name: str) -> int:
    import re

    test = {
        "like": lambda v: re.search("special.*requests", v) is not None,
        "not_like": lambda v: not v.startswith("plain"),
        "in": lambda v: v in ("plain 3", "plain 5", "absent"),
        "between": lambda v: "plain 2" <= v <= "plain 4",
        "less": lambda v: v < "plain 3",
    }[name]
    return sum(1 for v in values if test(v))


@pytest.mark.parametrize("name", sorted(PREDICATES))
def test_second_bind_visits_nothing(sess, name):
    sql = f"select count(*) from t where {PREDICATES[name]}"
    w0 = walked(sess)
    assert sess.execute(sql).rows() == [(expected(notes(40), name),)]
    w1 = walked(sess)
    assert (w1[0] - w0[0], w1[1] - w0[1]) == (1, 40)
    assert sess.execute(sql).rows() == [(expected(notes(40), name),)]
    assert walked(sess) == w1


@pytest.mark.parametrize("name", sorted(PREDICATES))
def test_grown_dictionary_is_walked_over_its_tail(sess, name):
    sql = f"select count(*) from t where {PREDICATES[name]}"
    sess.execute(sql)
    more = notes(24, start=40) + ["plain 3"]  # one value it already has
    rows = ", ".join(f"({100 + i}, '{v}')" for i, v in enumerate(more))
    sess.execute(f"insert into t (k, note) values {rows}")
    w0 = walked(sess)
    assert sess.execute(sql).rows() == [
        (expected(notes(40) + more, name),)]
    w1 = walked(sess)
    assert (w1[0] - w0[0], w1[1] - w0[1]) == (1, 24)  # the new values
    assert sess.execute(sql).rows() == [
        (expected(notes(40) + more, name),)]
    assert walked(sess) == w1


def test_remap_operand_is_walked_as_it_was(sess):
    """A string function's output dictionary lives in the expression
    (BStrRemap), not in the store: no memo, no counter, same answer."""
    sql = "select count(*) from t where substring(note from 1 for 5) " \
          "in ('plain', 'other')"
    w0 = walked(sess)
    for _ in range(2):
        assert sess.execute(sql).rows() == [(30,)]
    assert walked(sess) == w0


def test_codes_where_keys_entries_and_bounds_them():
    d = Dictionary()
    d.intern_array(["a", "b", "ab", "c"])
    assert d.codes_where(("like", "a%"), lambda v: v.startswith("a")) \
        == ((0, 2), 4)
    assert d.codes_where(("like", "a%"), lambda v: v.startswith("a")) \
        == ((0, 2), 0)
    # another key is another walk, whatever the lambda
    assert d.codes_where(("like", "%b"), lambda v: v.endswith("b")) \
        == ((1, 2), 4)
    d.intern_array(["abc", "b"])
    assert d.codes_where(("like", "a%"), lambda v: v.startswith("a")) \
        == ((0, 2, 4), 1)
    for i in range(2 * Dictionary.PREDICATE_MEMO_MAX):
        d.codes_where(("=", str(i)), lambda v: False)
    assert len(d._pred_memo) == Dictionary.PREDICATE_MEMO_MAX
    assert d.codes_where(("like", "a%"), lambda v: v.startswith("a")) \
        == ((0, 2, 4), 5)  # evicted: walked again, the same codes


def test_codes_where_bounds_the_codes_it_holds(monkeypatch):
    """Entries are bounded by the codes they hold between them, not
    only by their number: a predicate that matches most of a large
    dictionary may not pin it 32 times over."""
    monkeypatch.setattr(Dictionary, "PREDICATE_MEMO_MAX_CODES", 10)
    d = Dictionary()
    d.intern_array([f"v{i}" for i in range(8)])
    assert d.codes_where(("like", "v%"), lambda v: True) \
        == (tuple(range(8)), 8)
    assert d.codes_where(("=", "v1"), lambda v: v == "v1") == ((1,), 8)
    assert d.codes_where(("like", "v%"), lambda v: True)[1] == 0  # held
    assert d.codes_where(("<", "v3"), lambda v: v < "v3") \
        == ((0, 1, 2), 8)
    # 1 + 8 + 3 codes pass the bound: the least recently used go
    # until what is held fits
    assert list(d._pred_memo) == [("<", "v3")]
    # one entry over the bound alone is not kept, and still answered
    d.intern_array([f"w{i}" for i in range(20)])
    assert len(d.codes_where(("like", "%"), lambda v: True)[0]) == 28
    assert ("like", "%") not in d._pred_memo


def test_bound_plan_is_what_it_was(sess):
    """The memo changes where the codes come from, not the bound
    expression: a negated BInConst over the matching codes."""
    from citus_tpu.planner.bind import Binder
    from citus_tpu.session import _StoreDicts
    from citus_tpu.sql import parse

    sel = parse("select k from t where note not like '%special%'")[0]
    binder = Binder(sess.catalog, _StoreDicts(sess.store))
    first = binder.bind_select(sel)
    again = binder.bind_select(sel)
    assert repr(first) == repr(again)
    found = [c for c in first.conjuncts if isinstance(c, ir.BInConst)]
    assert len(found) == 1 and found[0].negated
    assert found[0].values == tuple(range(0, 40, 4))
