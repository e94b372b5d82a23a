"""`SqliteStore.put_many` since ISSUE 45: a segment goes to sqlite in
multi-row statements of at most 499 rows, inside the one transaction it
always had.  What it leaves is what row-by-row `put` leaves, byte for
byte; a round given twice keeps the later row; any exception leaves the
table as it was; no other connection sees a row before the COMMIT; and
the enclosing span carries the number of statements."""

import sqlite3

import pytest

from drand_tpu import tracing
from drand_tpu.chain import store as store_mod
from drand_tpu.chain.beacon import Beacon
from drand_tpu.chain.store import SqliteStore

ROWS = store_mod._ROWS_A_STATEMENT
SIZES = [0, 1, 353, 499, 500, 16383, 16384]


def _beacons(n, fields, first=1, salt=0):
    """n rows from round `first`: a signature alone (an unchained
    scheme's row) or with the previous one (a chained scheme's)."""
    sig = lambda r: (r + salt).to_bytes(4, "big") * 12
    return [Beacon(round=r, signature=sig(r),
                   previous_sig=sig(r - 1) if fields == 2 else b"")
            for r in range(first, first + n)]


def _table(store):
    """Every row's round and bytes, as the file holds them."""
    return store.raw_rows(0, 1 << 30)


def test_a_statement_holds_998_variables():
    assert ROWS == 499 and 2 * ROWS < 999
    assert store_mod._insert_rows_sql(3).endswith(
        "VALUES (?, ?), (?, ?), (?, ?)")


@pytest.mark.parametrize("fields", [1, 2], ids=["one_field", "two_fields"])
@pytest.mark.parametrize("n", SIZES)
def test_put_many_leaves_what_row_by_row_put_leaves(tmp_path, n, fields):
    beacons = _beacons(n, fields)
    many = SqliteStore(str(tmp_path / "many.db"))
    many.put_many(iter(beacons))            # any iterable, as before
    single = SqliteStore(str(tmp_path / "single.db"))
    with single._conn() as conn:            # `put`'s statement, one commit
        for b in beacons:
            conn.execute(store_mod._insert_rows_sql(1),
                         (b.round, single._encode(b)))
    if n:                                   # and `put` itself
        single.put(beacons[-1])
    assert _table(many) == _table(single)
    assert len(many) == n
    if n:
        assert many.last().equal(beacons[-1])
        assert many.get(beacons[n // 2].round).equal(beacons[n // 2])


@pytest.mark.parametrize("n", SIZES)
def test_the_span_counts_the_statements(tmp_path, n):
    store = SqliteStore(str(tmp_path / "s.db"))
    with tracing.span("store.commit", rows=n) as sp:
        store.put_many(_beacons(n, 1))
    assert sp.attrs["statements"] == -(-n // ROWS)
    assert {"encode_s", "insert_s", "flush_s"} <= set(sp.attrs)
    # added, as the seconds are: a second segment under the same span
    with tracing.span("check.overwrite") as sp:
        store.put_many(_beacons(500, 1))
        store.put_many(_beacons(353, 1, first=501))
    assert sp.attrs["statements"] == 2 + 1


@pytest.mark.parametrize("second_at", [7, ROWS - 1, ROWS, 2 * ROWS + 5],
                         ids=["same_statement", "statements_last_row",
                              "next_statement", "two_statements_on"])
def test_a_round_given_twice_keeps_the_later_row(tmp_path, second_at):
    beacons = _beacons(3 * ROWS, 2)
    later = _beacons(1, 2, first=5, salt=99)[0]
    beacons.insert(second_at, later)
    store = SqliteStore(str(tmp_path / "twice.db"))
    store.put_many(beacons)
    assert len(store) == 3 * ROWS
    assert store.get(5).equal(later)
    # and the earlier one where it comes later
    store.put_many([later] + _beacons(ROWS, 2))
    assert store.get(5).equal(_beacons(1, 2, first=5)[0])


class _Disk(RuntimeError):
    pass


@pytest.mark.parametrize("what", ["a_row_whose_encoder_raises",
                                  "a_statement_fails_after_two_ran",
                                  "the_commit_fails"])
def test_an_exception_midway_leaves_the_table_as_it_was(tmp_path, what):
    store = SqliteStore(str(tmp_path / "rb.db"))
    store.put_many(_beacons(700, 1, salt=1))
    before = _table(store)
    segment = _beacons(3 * ROWS, 1, first=600)      # overwrites and adds
    bad_round = segment[2 * ROWS + 10].round        # in the third statement
    encode = store._encode
    if what == "a_row_whose_encoder_raises":
        def enc(b):
            if b.round == bad_round:
                raise _Disk(b.round)
            return encode(b)
        store._encode, raised = enc, _Disk
    elif what == "a_statement_fails_after_two_ran":
        # NOT NULL on `data`: the third statement fails in sqlite itself
        store._encode = lambda b: None if b.round == bad_round else encode(b)
        raised = sqlite3.IntegrityError
    else:
        class Conn(sqlite3.Connection):
            def commit(self):
                raise _Disk("commit")
        store._local.conn = sqlite3.connect(store.path, factory=Conn)
        raised = _Disk
    with pytest.raises(raised):
        store.put_many(segment)
    assert not store._conn().in_transaction
    other = SqliteStore(store.path)
    assert _table(other) == before == _table(store)
    # the connection is fit for the next segment
    store._encode = encode
    store._local.conn = None
    store.put_many(segment)
    assert len(store) == 599 + 3 * ROWS


@pytest.mark.parametrize("n", [1, ROWS + 1, 16384])
def test_one_transaction_a_segment(tmp_path, n):
    """A second connection sees none of the segment's rows after any of
    its statements, and all of them after the one COMMIT."""
    path = str(tmp_path / "tx.db")
    store = SqliteStore(path)
    store.put_many(_beacons(10, 1))
    seen = {"statements": 0, "commits": 0, "rows_before_commit": []}
    reader = sqlite3.connect(path)
    count = lambda: reader.execute(
        "SELECT COUNT(*) FROM beacons").fetchone()[0]

    class Conn(sqlite3.Connection):
        def execute(self, sql, *args):
            out = super().execute(sql, *args)
            if sql.startswith("INSERT"):
                seen["statements"] += 1
                assert self.in_transaction
                seen["rows_before_commit"].append(count())
            return out

        def commit(self):
            seen["commits"] += 1
            super().commit()

    conn = sqlite3.connect(path, timeout=30, factory=Conn)
    conn.execute("PRAGMA journal_mode=WAL")
    conn.execute(f"PRAGMA synchronous={store._sync_level}")
    store._local.conn = conn
    store.put_many(_beacons(n, 1, first=11))
    assert seen["statements"] == -(-n // ROWS) and seen["commits"] == 1
    assert set(seen["rows_before_commit"]) == {10}
    assert count() == 10 + n
    reader.close()


def test_the_synchronous_level_is_the_connections(tmp_path, monkeypatch):
    """`put_many` sets no pragma of its own: after a segment the
    connection's level is the one the store was opened with."""
    for level, number in (("NORMAL", 1), ("FULL", 2)):
        monkeypatch.setenv(store_mod.SYNC_ENV, level)
        store = SqliteStore(str(tmp_path / f"{level}.db"))
        store.put_many(_beacons(ROWS + 1, 1))
        assert store._conn().execute(
            "PRAGMA synchronous").fetchone()[0] == number
        assert store._conn().execute(
            "PRAGMA journal_mode").fetchone()[0] == "wal"
