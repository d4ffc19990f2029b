"""Data-plane tests: golden lines for every text format the reference's
ExampleParser handles (data/text_parser.cc: libsvm, criteo, adfea, terafea,
ps dense/sparse/sparse_binary), C++-vs-Python parser parity, and protobuf-text
config parsing of every shipped example conf (example/linear/*/*.conf)."""

import glob
import os

import numpy as np
import pytest

from parameter_server_tpu.apps.linear.config import parse_conf
from parameter_server_tpu.data.text_parser import (
    SLOT_SPACE,
    ExampleParser,
    parse_adfea,
    parse_criteo,
    parse_libsvm,
    parse_ps_dense,
    parse_ps_sparse,
    parse_ps_sparse_binary,
    parse_terafea,
)

CONF_DIR = os.path.join(os.path.dirname(__file__), "..", "configs")


class TestGoldenLines:
    def test_libsvm(self):
        b = parse_libsvm(["1 3:0.5 7:2", "-1 1:1", "0 2:4"])
        assert b.n == 3 and b.nnz == 4
        np.testing.assert_array_equal(b.y, [1, -1, -1])  # label>0 → +1 else -1
        np.testing.assert_array_equal(b.indices[:2], [3, 7])
        np.testing.assert_allclose(b.values[:3], [0.5, 2.0, 1.0])

    def test_libsvm_skips_garbage(self):
        b = parse_libsvm(["", "notalabel 1:2", "1 5:1"])
        assert b.n == 1 and b.indices[0] == 5

    def test_criteo(self):
        from parameter_server_tpu.data.text_parser import _CRITEO_STRIPE
        from parameter_server_tpu.utils.murmur import murmur3_x64_128

        line = "1\t" + "\t".join(str(i) for i in range(1, 14)) + "\t" + "\t".join(
            ["68fd1e64"] * 26
        )
        b = parse_criteo([line, line.replace("1\t", "0\t", 1)])
        assert b.n == 2 and b.nnz == 78 and b.binary
        np.testing.assert_array_equal(b.y, [1, -1])
        # reference key construction: integer slot i, count c -> binary key
        # kMaxKey/13*i + c (ParseCriteo, text_parser.cc)
        assert np.uint64(b.indices[0]) == np.uint64(1)  # i=0, cnt=1
        assert np.uint64(b.indices[12]) == np.uint64(
            (_CRITEO_STRIPE * 12 + 13) & ((1 << 64) - 1)
        )
        # categorical tokens: murmur3_x64_128 h0^h1, seed 512927377
        h0, h1 = murmur3_x64_128(b"68fd1e64", 512927377)
        assert np.uint64(b.indices[13]) == np.uint64(h0 ^ h1)

    def test_criteo_missing_fields(self):
        # EMPTY int fields parse as count 0 (strtoi32("") is a
        # successful no-conversion in the reference) -> key stripe*i+0;
        # short (<5 char) categorical tokens skipped; a line without
        # the 13 int tabs is dropped entirely
        ints = ["", "2"] + [""] * 11
        cats = ["abc"] + ["longtoken"] + [""] * 24
        b = parse_criteo(
            ["1\t" + "\t".join(ints) + "\t" + "\t".join(cats), "1\t2\t3"]
        )
        assert b.n == 1 and b.nnz == 14  # 13 int keys + 1 long cat
        from parameter_server_tpu.data.text_parser import _CRITEO_STRIPE

        # empty field 0 -> count 0; explicit "2" in slot i=1 -> count 2
        assert np.uint64(b.indices[0]) == np.uint64(0)
        assert np.uint64(b.indices[1]) == np.uint64(
            (_CRITEO_STRIPE * 1 + 2) & ((1 << 64) - 1)
        )

    def test_criteo_python_matches_native(self):
        from parameter_server_tpu.data.text_parser import _parse_native

        rng = np.random.default_rng(3)
        lines = []
        for _ in range(50):
            ints = [str(rng.integers(-2, 50)) if rng.random() > 0.2 else "" for _ in range(13)]
            cats = [f"{rng.integers(0, 1 << 32):08x}" if rng.random() > 0.3 else "ab" for _ in range(26)]
            lines.append(f"{rng.integers(0, 2)}\t" + "\t".join(ints) + "\t" + "\t".join(cats))
        py = parse_criteo(lines)
        cc = _parse_native(("\n".join(lines) + "\n").encode(), "ps_parse_criteo", 60)
        np.testing.assert_array_equal(py.indices, cc.indices)
        np.testing.assert_array_equal(py.indptr, cc.indptr)
        np.testing.assert_array_equal(py.y, cc.y)

    def test_adfea(self):
        # ref ParseAdfea tokens (split on " :"): line_id, "1", label, then
        # key:slot pairs — text_parser.cc:90-121
        b = parse_adfea(["100 1 1 123:4 456:7", "101 1 0 789:2"])
        assert b.n == 2 and b.nnz == 3
        np.testing.assert_array_equal(b.y, [1, -1])
        assert b.indices[0] == 4 * SLOT_SPACE + 123
        assert b.indices[2] == 2 * SLOT_SPACE + 789
        assert b.binary

    def test_terafea(self):
        # ref ParseTerafea: "label line_id separator key key ..."; group id
        # rides in key >> 54, whole key is the feature id
        k1 = (3 << 54) | 123
        k2 = (3 << 54) | 456
        k3 = (9 << 54) | 123
        b = parse_terafea([f"1 1000 | {k1} {k2} {k3}", f"-1 1001 | {k1}"])
        assert b.n == 2 and b.nnz == 4
        np.testing.assert_array_equal(b.y, [1, -1])
        # whole-key identity: same key maps identically across rows,
        # different group bits keep same low bits distinct
        assert b.indices[0] == b.indices[3] == k1
        assert b.indices[2] == k3 != k1

    def test_ps_sparse(self):
        b = parse_ps_sparse(["1;2 3:0.5 4:1.5;7 9:2;", "-1;2 3:1;"])
        assert b.n == 2 and b.nnz == 4
        assert b.indices[0] == 2 * SLOT_SPACE + 3
        assert b.indices[2] == 7 * SLOT_SPACE + 9
        np.testing.assert_allclose(b.values[:3], [0.5, 1.5, 2.0])

    def test_ps_sparse_binary(self):
        b = parse_ps_sparse_binary(["1;2 3 4;7 9;", "0;2 3;"])
        assert b.n == 2 and b.nnz == 4 and b.binary
        np.testing.assert_array_equal(b.y, [1, -1])
        assert b.indices[0] == 2 * SLOT_SPACE + 3
        assert b.indices[2] == 7 * SLOT_SPACE + 9

    def test_ps_dense(self):
        b = parse_ps_dense(["1;2 0.5 1.5 2.5;", "-1;2 9;"])
        assert b.n == 2 and b.nnz == 4
        # positional keys within the group stripe
        np.testing.assert_array_equal(
            b.indices[:3] - 2 * SLOT_SPACE, [0, 1, 2]
        )
        np.testing.assert_allclose(b.values[:3], [0.5, 1.5, 2.5])


class TestNativeParity:
    """The C++ fast path must produce byte-identical CSR output to the
    Python fallback (ref: one parser, two deployments)."""

    @pytest.mark.parametrize("fmt,lines", [
        (
            "libsvm",
            ["1 3:0.5 7:2", "-1 1:1 2:0.25 9:4", "1 5:1"],
        ),
        (
            "criteo",
            [
                "1\t" + "\t".join(str(i) for i in range(1, 14))
                + "\t" + "\t".join(["68fd1e64", "80e26c9b"] * 13),
                # well-formed line with empty numeric/categorical fields
                # (the common Criteo missing-value shape)
                "0\t" + "\t".join(["", "2", ""] + [str(i) for i in range(3, 13)])
                + "\t" + "\t".join((["a1b2c3", ""] * 13)),
            ],
        ),
    ])
    def test_native_matches_python(self, fmt, lines):
        native = ExampleParser(fmt, use_native=True)
        python = ExampleParser(fmt, use_native=False)
        if not native.use_native:
            pytest.skip("native lib unavailable")
        a, b = native.parse_lines(lines), python.parse_lines(lines)
        np.testing.assert_array_equal(a.y, b.y)
        np.testing.assert_array_equal(a.indptr, b.indptr)
        np.testing.assert_array_equal(a.indices, b.indices)
        assert a.binary == b.binary
        if not a.binary:
            np.testing.assert_allclose(a.values, b.values)
        np.testing.assert_array_equal(a.slot_ids, b.slot_ids)


class TestParserFuzz:
    """Seeded mutation fuzz: the C++ fast paths must stay BIT-EXACT with
    the Python parsers on mangled input, not just on well-formed lines —
    truncations, garbage bytes, doubled separators, blank lines, and
    spliced fragments (the classes behind every past parity bug)."""

    def _mutate(self, rng, line: str) -> str:
        ops = rng.integers(0, 7)
        if ops == 0 and len(line) > 2:  # truncate anywhere
            return line[: rng.integers(1, len(line))]
        if ops == 5 and "\t" in line:  # empty out one criteo field
            f = line.split("\t")
            f[int(rng.integers(0, len(f)))] = ""
            return "\t".join(f)
        if ops == 6 and line:  # long leading-zero run before a digit
            # (strtoull/strtol accumulate magnitude — a digit-COUNT
            # overflow guard must not clamp '00…07' to ULLONG_MAX)
            i = rng.integers(0, len(line))
            return line[:i] + "0" * int(rng.integers(15, 30)) + line[i:]
        if ops == 1:  # inject a garbage byte
            i = rng.integers(0, len(line) + 1)
            ch = chr(rng.integers(33, 127))
            return line[:i] + ch + line[i:]
        if ops == 2 and line:  # double a separator
            i = rng.integers(0, len(line))
            return line[:i] + ("\t" if rng.random() < 0.5 else " ") + line[i:]
        if ops == 3:  # blank/whitespace-only line
            return " " * int(rng.integers(0, 4))
        if ops == 4 and len(line) > 4:  # splice two halves of itself
            i = rng.integers(1, len(line) - 1)
            return line[i:] + line[:i]
        return line

    def _wellformed(self, rng, fmt: str) -> str:
        if fmt == "criteo":
            ints = "\t".join(str(rng.integers(0, 100)) for _ in range(13))
            cats = "\t".join(
                f"{rng.integers(0, 1 << 32):08x}" for _ in range(26)
            )
            return f"{rng.integers(0, 2)}\t{ints}\t{cats}"
        # libsvm: ragged sparse rows, occasional explicit values;
        # indices SORTED — the strict parser drops unordered lines, and
        # unsorted generation would leave the value-parity path barely
        # exercised (mutations still cover the unordered-drop case)
        n = rng.integers(1, 6)
        idxs = np.sort(rng.integers(1, 1 << 20, size=n))
        feats = " ".join(
            f"{i}:{rng.integers(1, 5)}" if rng.random() < 0.5 else f"{i}:1"
            for i in idxs
        )
        return f"{(-1) ** rng.integers(0, 2)} {feats}"

    @pytest.mark.parametrize("fmt", ["libsvm", "criteo"])
    def test_mutated_lines_stay_bit_exact(self, fmt):
        native = ExampleParser(fmt, use_native=True)
        python = ExampleParser(fmt, use_native=False)
        if not native.use_native:
            pytest.skip("native lib unavailable")
        rng = np.random.default_rng(0)
        for trial in range(200):
            lines = []
            for _ in range(int(rng.integers(1, 8))):
                line = self._wellformed(rng, fmt)
                if rng.random() < 0.7:
                    line = self._mutate(rng, line)
                lines.append(line)
            a = native.parse_lines(lines)
            b = python.parse_lines(lines)
            ctx = f"trial {trial}: {lines!r}"
            np.testing.assert_array_equal(a.y, b.y, err_msg=ctx)
            np.testing.assert_array_equal(a.indptr, b.indptr, err_msg=ctx)
            np.testing.assert_array_equal(a.indices, b.indices, err_msg=ctx)
            assert a.binary == b.binary, ctx
            if not a.binary:
                # BIT-exact, not approximately equal — a 1-ulp strtod/
                # float() divergence is exactly what this test hunts
                np.testing.assert_array_equal(a.values, b.values, err_msg=ctx)
            np.testing.assert_array_equal(a.slot_ids, b.slot_ids, err_msg=ctx)

    def test_empty_tokens_parse_as_zero_like_reference(self):
        """strtonum.h treats strtoull("")/strtof("")/strtol("") as
        success with 0 (no conversion, end at the terminator). So
        ":5" is feature id 0, "7:" is value 0, an empty criteo label
        is class -1, and an EMPTY criteo int field emits key
        stripe*i+0 (that's how real criteo marks missing ints)."""
        for fmt in ("libsvm", "criteo"):
            python = ExampleParser(fmt, use_native=False)
            native = ExampleParser(fmt, use_native=True)
            if fmt == "libsvm":
                lines = ["1 :5 9:", "-1 :"]
                a = python.parse_lines(lines)
                assert a.y.tolist() == [1.0, -1.0]
                assert a.indices.tolist() == [0, 9, 0]
                assert a.values.tolist() == [5.0, 0.0, 0.0]
            else:
                ints = ["1"] * 13
                ints[3] = ""          # missing int -> key stripe*3 + 0
                cats = ["deadbeef"] * 26
                lines = ["\t".join([""] + ints + cats)]  # empty label
                a = python.parse_lines(lines)
                assert a.y.tolist() == [-1.0]  # label 0 -> negative
                from parameter_server_tpu.data.text_parser import (
                    _CRITEO_STRIPE,
                )
                assert (_CRITEO_STRIPE * 3) in (
                    np.asarray(a.indices, np.uint64).tolist()
                )
            if native.use_native:
                b = native.parse_lines(lines)
                np.testing.assert_array_equal(a.y, b.y)
                np.testing.assert_array_equal(a.indices, b.indices)
                np.testing.assert_array_equal(a.indptr, b.indptr)
                if not a.binary:
                    np.testing.assert_array_equal(a.values, b.values)

    @pytest.mark.parametrize("fmt,lines,want_indices", [
        # strtoull accumulates: 21 digits of mostly zeros is 7, not a
        # clamp to ULLONG_MAX (which would also drop the line as
        # unordered since ULLONG_MAX > 9 fails the sorted-ids check)
        ("libsvm", ["1 000000000000000000007:1 9:1"], [7, 9]),
        # criteo integer field: 20 zero-padded digits parse to key 5
        # in slot 6 (stripe 5), not strtol-ERANGE
        ("criteo", None, None),
    ])
    def test_leading_zero_runs_parse_by_magnitude(self, fmt, lines, want_indices):
        python = ExampleParser(fmt, use_native=False)
        native = ExampleParser(fmt, use_native=True)
        if fmt == "criteo":
            ints = ["1"] * 13
            ints[5] = "00000000000000000005"
            cats = ["00000000"] * 26
            lines = ["0\t" + "\t".join(ints + cats)]
        a = python.parse_lines(lines)
        if fmt == "libsvm":
            assert a.indices.tolist() == want_indices, a.indices
        else:
            from parameter_server_tpu.data.text_parser import _CRITEO_STRIPE
            assert (_CRITEO_STRIPE * 5 + 5) in a.indices.tolist()
        if native.use_native:
            b = native.parse_lines(lines)
            np.testing.assert_array_equal(a.indices, b.indices)
            np.testing.assert_array_equal(a.indptr, b.indptr)


class TestPythonOnlyParserRobustness:
    """adfea/terafea/ps_* have no native twin to diverge from, but they
    must never RAISE on mangled input and must always emit a consistent
    CSR (monotone indptr, matching array lengths)."""

    def _check_csr(self, b):
        assert b.indptr[0] == 0
        assert (np.diff(b.indptr) >= 0).all()
        assert b.indptr[-1] == len(b.indices)
        assert len(b.y) == len(b.indptr) - 1
        if b.values is not None:
            assert len(b.values) == len(b.indices)
        if b.slot_ids is not None:
            assert len(b.slot_ids) == len(b.indices)

    def test_mangled_lines_never_raise(self):
        from parameter_server_tpu.data.text_parser import (
            parse_ps_dense,
            parse_ps_sparse,
            parse_ps_sparse_binary,
        )

        parsers = {
            "adfea": parse_adfea,
            "terafea": parse_terafea,
            "ps_sparse": parse_ps_sparse,
            "ps_sparse_binary": parse_ps_sparse_binary,
            "ps_dense": parse_ps_dense,
        }
        seeds = {
            "adfea": "100 1 1 123:4 456:7",
            "terafea": "1 1000 | 123 456",
            "ps_sparse": "1;2 3:0.5 4:1.5;7 9:2;",
            "ps_sparse_binary": "1;2 3 4;7 9;",
            "ps_dense": "1;2 0.5 1.5 2.5;",
        }
        rng = np.random.default_rng(7)
        for name, fn in parsers.items():
            base = seeds[name]
            for trial in range(200):
                line = base
                for _ in range(int(rng.integers(1, 4))):
                    op = rng.integers(0, 5)
                    if op == 0 and len(line) > 2:
                        line = line[: rng.integers(1, len(line))]
                    elif op == 1:
                        i = rng.integers(0, len(line) + 1)
                        line = line[:i] + chr(rng.integers(33, 127)) + line[i:]
                    elif op == 2 and line:
                        i = rng.integers(0, len(line))
                        line = line[:i] + (";" if rng.random() < 0.5 else ":") + line[i:]
                    elif op == 3:
                        line = ""
                    elif op == 4 and len(line) > 4:
                        i = rng.integers(1, len(line) - 1)
                        line = line[i:] + line[:i]
                b = fn([line, base])  # mangled + a good line
                self._check_csr(b)
                assert b.n >= 1, (name, line)  # the good line always survives


class TestSlotIds:
    """Per-entry feature-group slots, matching the reference Example proto
    (text_parser.cc Slot.set_id: libsvm → 1; criteo int i → i+1, cat i →
    i+14; adfea/ps → group id; terafea → key >> 54)."""

    def test_criteo_slots(self):
        line = "1\t" + "\t".join(str(i) for i in range(1, 14)) + "\t" + "\t".join(
            ["68fd1e64"] * 26
        )
        b = parse_criteo([line])
        np.testing.assert_array_equal(b.slot_ids[:13], np.arange(1, 14))
        np.testing.assert_array_equal(b.slot_ids[13:], np.arange(14, 40))

    def test_criteo_truncated_cat_line_dropped(self):
        # ref ParseCriteo: a tab missing before the 25th categorical field
        # (i != 25) returns false — the whole line is dropped
        good = "1\t" + "\t".join(str(i) for i in range(1, 14)) + "\t" + "\t".join(
            ["68fd1e64"] * 26
        )
        truncated = "1\t" + "\t".join(str(i) for i in range(1, 14)) + "\t" + "\t".join(
            ["68fd1e64"] * 10
        )
        for use_native in (False, True):
            p = ExampleParser("criteo", use_native=use_native)
            if use_native and not p.use_native:
                continue
            b = p.parse_lines([good, truncated, good])
            assert b.n == 2, "truncated line must be dropped"

    def test_libsvm_slots(self):
        b = parse_libsvm(["1 3:0.5 7:2", "-1 1:1"])
        np.testing.assert_array_equal(b.slot_ids, [1, 1, 1])

    def test_adfea_slots(self):
        b = parse_adfea(["100 1 1 123:4 456:7", "101 1 0 789:2"])
        np.testing.assert_array_equal(b.slot_ids, [4, 7, 2])

    def test_terafea_slots(self):
        k1, k2 = (3 << 54) | 123, (9 << 54) | 456
        b = parse_terafea([f"1 1000 | {k1} {k2}"])
        np.testing.assert_array_equal(b.slot_ids, [3, 9])

    def test_ps_sparse_slots(self):
        b = parse_ps_sparse(["1;2 3:0.5 4:1.5;7 9:2;"])
        np.testing.assert_array_equal(b.slot_ids, [2, 2, 7])

    def test_record_roundtrip_keeps_slots(self):
        from parameter_server_tpu.data.example import batch_from_bytes, batch_to_bytes

        b = parse_criteo(
            [
                "1\t" + "\t".join(str(i) for i in range(1, 14)) + "\t"
                + "\t".join(["68fd1e64"] * 26)
            ]
        )
        rt = batch_from_bytes(batch_to_bytes(b))
        np.testing.assert_array_equal(rt.slot_ids, b.slot_ids)
        np.testing.assert_array_equal(rt.indices, b.indices)

    def test_slice_and_localize_keep_slots(self):
        from parameter_server_tpu.utils.localizer import remap

        b = parse_libsvm(["1 3:0.5 7:2", "-1 1:1", "1 9:2"])
        s = b.slice_rows(0, 2)
        np.testing.assert_array_equal(s.slot_ids, [1, 1, 1])
        kept = remap(b, np.array([1, 3, 9], dtype=np.int64))
        assert kept.slot_ids is not None and len(kept.slot_ids) == kept.nnz


class TestShippedConfigs:
    """Every conf under configs/ must parse (mirrors the reference's
    example/linear/* protobuf-text files driving main.cc)."""

    @pytest.mark.parametrize(
        "path",
        sorted(glob.glob(os.path.join(CONF_DIR, "*", "*.conf"))),
        ids=lambda p: "/".join(p.split(os.sep)[-2:]),
    )
    def test_parses(self, path):
        conf = parse_conf(open(path).read())
        assert conf.training_data or conf.validation_data
        if "batch" in os.path.basename(path) and "eval" not in os.path.basename(path):
            assert conf.darlin is not None
        if "online" in os.path.basename(path) and "eval" not in os.path.basename(path):
            assert conf.async_sgd is not None
        if "eval" in os.path.basename(path):
            assert conf.model_input is not None and conf.validation_data is not None


class TestFileMatching:
    def test_expand_globs_reference_regex(self, tmp_path):
        """Reference configs use basename REGEX patterns like "part.*"
        (data/common.cc searchFiles) — they must match part-0, part-1."""
        from parameter_server_tpu.utils import file as psfile

        d = tmp_path / "train"
        d.mkdir()
        for name in ("part-0", "part-1", "other.txt"):
            (d / name).write_text("x")
        hits = psfile.expand_globs([str(d / "part.*")])
        assert [os.path.basename(h) for h in hits] == ["part-0", "part-1"]
        # shell glob still works and wins when it matches
        hits = psfile.expand_globs([str(d / "*.txt")])
        assert [os.path.basename(h) for h in hits] == ["other.txt"]
        # regex is anchored: "art.*" must not match "part-0"
        assert psfile.expand_globs([str(d / "art.*")]) == []


class TestByteStreaming:
    """Chunked byte path (StreamReader.minibatches_bytes / parse_text):
    must yield exactly the same minibatches as the line path — chunk
    boundaries, thread-pool ordering and the tail batch included."""

    def _write_criteo(self, path, rows, seed=0):
        rng = np.random.default_rng(seed)
        with open(path, "w") as f:
            for i in range(rows):
                ints = "\t".join(str(v) for v in rng.integers(0, 50, 13))
                cats = "\t".join(
                    f"{v:08x}" for v in rng.integers(0, 1 << 24, 26)
                )
                f.write(f"{i % 2}\t{ints}\t{cats}\n")

    def test_matches_line_path(self, tmp_path):
        from parameter_server_tpu.data.stream_reader import StreamReader

        p = tmp_path / "part-0"
        self._write_criteo(str(p), rows=997)
        line_batches = list(StreamReader([str(p)], "criteo").minibatches(256))
        byte_batches = list(
            StreamReader([str(p)], "criteo").minibatches_bytes(
                256, chunk_bytes=1 << 14, threads=3
            )
        )
        assert len(line_batches) == len(byte_batches) == 4
        for a, b in zip(line_batches, byte_batches):
            np.testing.assert_array_equal(a.y, b.y)
            np.testing.assert_array_equal(a.indptr, b.indptr)
            np.testing.assert_array_equal(a.indices, b.indices)
            np.testing.assert_array_equal(a.slot_ids, b.slot_ids)

    def test_parse_text_equals_parse_lines(self):
        lines = ["1 3:1 7:2", "-1 1:4 9:1"]
        p = ExampleParser("libsvm")
        a = p.parse_lines(lines)
        b = p.parse_text(("\n".join(lines) + "\n").encode())
        np.testing.assert_array_equal(a.indices, b.indices)
        np.testing.assert_array_equal(a.y, b.y)


class TestLibsvmFastPaths:
    """Regression for the manual-parse fast paths (label, index, integer
    value): must stay bit-exact with the Python parser on floats,
    exponents, and values beyond double's exact-integer range."""

    def test_native_matches_python_on_edge_values(self):
        sample = (
            "+1 3:1 7:0.25 9:2\n"
            "-1 1:1e-3 2:1\n"
            "0 5:1\n"
            "2.5 4:9007199254740993\n"  # 2^53+1: must take the strtod path
        )
        p = ExampleParser("libsvm")
        a = p.parse_text(sample.encode())
        c = parse_libsvm(sample.splitlines())
        np.testing.assert_array_equal(a.y, c.y)
        np.testing.assert_array_equal(a.indptr, c.indptr)
        np.testing.assert_allclose(a.values, c.values, rtol=0)
        # an index beyond uint64 clamps (strtoull ERANGE semantics) in the
        # native parser — no wraparound key (the Python parser cannot even
        # represent it in int64, so no cross-check)
        big = p.parse_text(b"1 18446744073709551999:1\n")
        assert big.indices.view(np.uint64)[0] == np.uint64(2**64 - 1)

    def test_signed_index_empty_value_and_ws_lines(self):
        """Review scenarios: '+3:'/'-3:' signed indices (strtoull modulo
        semantics), empty value tokens defaulting to 1.0, and
        whitespace-only lines — native must match the Python parser."""
        sample = "+1 +3:1 -3:2\n1 3:\n1 3: 4:1\n \n1 5:2\n"
        p = ExampleParser("libsvm")
        a = p.parse_text(sample.encode())
        c = parse_libsvm(sample.splitlines())
        np.testing.assert_array_equal(a.y, c.y)
        np.testing.assert_array_equal(a.indptr, c.indptr)
        np.testing.assert_array_equal(a.indices, c.indices)
        np.testing.assert_allclose(a.values, c.values, rtol=0)

    def test_criteo_tabs_only_line_is_all_zero_row(self):
        """A tabs-only line parses as a valid ALL-MISSING row in the
        reference (strtofloat("")/strtoi32("") succeed with 0): label 0
        -> class -1, 13 zero-count int keys, no cats. The parse must
        still not let strtod cross the newline and steal the next
        line's label."""
        tabs_only = "\t" * 39 + "\n"
        good = (
            "1\t" + "\t".join("2" for _ in range(13)) + "\t"
            + "\t".join("LONGTOK%d" % i for i in range(26)) + "\n"
        )
        b = ExampleParser("criteo").parse_text((tabs_only + good).encode())
        assert b.n == 2
        assert b.y.tolist() == [-1.0, 1.0]  # "" label did NOT eat the 1
        assert b.indptr[1] - b.indptr[0] == 13  # 13 empty-int keys
