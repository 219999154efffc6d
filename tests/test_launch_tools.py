"""launch/hloparse.py and launch/roofline.py — previously untested.

hloparse: text fixtures exercise computation splitting, collective byte
accounting, while-loop trip-count attribution (including nesting), and the
-start/-done async-pair rules. roofline: the three-term arithmetic against
the analytic FLOP model, dry-run artifact merging, and the table printer.
"""
import json
import textwrap

import pytest

from repro.launch import hloparse, roofline

# ---------------------------------------------------------------------------
# hloparse fixtures
# ---------------------------------------------------------------------------

# one scan (12 trips) holding an all-reduce, plus a top-level all-gather
SCAN_HLO = textwrap.dedent("""\
    HloModule test_scan

    %add (a: f32[], b: f32[]) -> f32[] {
      %a = f32[] parameter(0)
      %b = f32[] parameter(1)
      ROOT %s = f32[] add(f32[] %a, f32[] %b)
    }

    %scan_cond (p: (s32[], f32[8,128])) -> pred[] {
      %iter = s32[] get-tuple-element((s32[], f32[8,128]) %p), index=0
      %limit = s32[] constant(12)
      ROOT %lt = pred[] compare(s32[] %iter, s32[] %limit), direction=LT
    }

    %scan_body (p: (s32[], f32[8,128])) -> (s32[], f32[8,128]) {
      %x = f32[8,128] get-tuple-element((s32[], f32[8,128]) %p), index=1
      %ar = f32[8,128] all-reduce(f32[8,128] %x), to_apply=%add
      ROOT %t = (s32[], f32[8,128]) tuple(%iter, %ar)
    }

    ENTRY %main (a: f32[32,128]) -> f32[64,128] {
      %a = f32[32,128] parameter(0)
      %ag = f32[64,128] all-gather(f32[32,128] %a), dimensions={0}
      %w = (s32[], f32[8,128]) while((s32[], f32[8,128]) %init), condition=%scan_cond, body=%scan_body
      ROOT %r = f32[64,128] copy(f32[64,128] %ag)
    }
    """)

# outer scan (3 trips) containing an inner scan (5 trips): multiply through
NESTED_HLO = textwrap.dedent("""\
    HloModule test_nested

    %inner_cond (p: (s32[], f32[4,128])) -> pred[] {
      %limit = s32[] constant(5)
      ROOT %lt = pred[] compare(s32[] %i, s32[] %limit), direction=LT
    }

    %inner_body (p: (s32[], f32[4,128])) -> (s32[], f32[4,128]) {
      %x = f32[4,128] get-tuple-element((s32[], f32[4,128]) %p), index=1
      %rs = f32[4,128] reduce-scatter(f32[4,128] %x), dimensions={0}
      ROOT %t = (s32[], f32[4,128]) tuple(%i, %rs)
    }

    %outer_cond (p: (s32[], f32[4,128])) -> pred[] {
      %limit = s32[] constant(3)
      ROOT %lt = pred[] compare(s32[] %i, s32[] %limit), direction=LT
    }

    %outer_body (p: (s32[], f32[4,128])) -> (s32[], f32[4,128]) {
      %w = (s32[], f32[4,128]) while((s32[], f32[4,128]) %p), condition=%inner_cond, body=%inner_body
      ROOT %t = (s32[], f32[4,128]) copy((s32[], f32[4,128]) %w)
    }

    ENTRY %main (a: f32[4,128]) -> f32[4,128] {
      %w = (s32[], f32[4,128]) while((s32[], f32[4,128]) %init), condition=%outer_cond, body=%outer_body
      ROOT %r = f32[4,128] get-tuple-element((s32[], f32[4,128]) %w), index=1
    }
    """)

ASYNC_HLO = textwrap.dedent("""\
    HloModule test_async

    ENTRY %main (a: f32[16,128]) -> f32[32,128] {
      %a = f32[16,128] parameter(0)
      %ags = f32[32,128] all-gather-start(f32[16,128] %a), dimensions={0}
      %agd = f32[32,128] all-gather-done(f32[32,128] %ags)
      ROOT %r = f32[32,128] copy(f32[32,128] %agd)
    }
    """)


class TestSplitComputations:
    def test_splits_and_names(self):
        comps = hloparse.split_computations(SCAN_HLO)
        assert set(comps) == {"add", "scan_cond", "scan_body", "main"}
        assert "all-reduce" in comps["scan_body"]
        assert "all-gather" in comps["main"]

    def test_empty_module(self):
        assert hloparse.split_computations("HloModule empty\n") == {}


class TestTripCount:
    def test_reads_largest_constant(self):
        comps = hloparse.split_computations(SCAN_HLO)
        assert hloparse._trip_count(comps["scan_cond"]) == 12

    def test_defaults_to_one_without_constants(self):
        assert hloparse._trip_count("ROOT %lt = pred[] compare(...)") == 1
        assert hloparse._trip_count("") == 1


class TestCollectiveBytes:
    def test_scan_multiplies_by_trip_count(self):
        by, cnt = hloparse.collective_bytes(SCAN_HLO)
        # all-gather at top level: 64*128*4 bytes, once
        assert by["all-gather"] == 64 * 128 * 4
        assert cnt["all-gather"] == 1
        # all-reduce inside the 12-trip scan: 8*128*4 bytes each trip
        assert by["all-reduce"] == 12 * 8 * 128 * 4
        assert cnt["all-reduce"] == 12
        assert by["reduce-scatter"] == 0

    def test_nested_scans_multiply_through(self):
        by, cnt = hloparse.collective_bytes(NESTED_HLO)
        assert cnt["reduce-scatter"] == 3 * 5
        assert by["reduce-scatter"] == 3 * 5 * 4 * 128 * 4

    def test_async_pair_counted_once(self):
        by, cnt = hloparse.collective_bytes(ASYNC_HLO)
        assert cnt["all-gather"] == 1            # -start counts, -done not
        assert by["all-gather"] == 32 * 128 * 4

    def test_empty_input_is_all_zero(self):
        by, cnt = hloparse.collective_bytes("")
        assert set(by) == set(hloparse.COLLECTIVES)
        assert all(v == 0 for v in by.values())
        assert all(v == 0 for v in cnt.values())

    def test_shape_bytes(self):
        assert hloparse._shape_bytes("f32", "8,128") == 8 * 128 * 4
        assert hloparse._shape_bytes("bf16", "1024") == 2048
        assert hloparse._shape_bytes("pred", "") == 1


# ---------------------------------------------------------------------------
# roofline
# ---------------------------------------------------------------------------

from repro.configs.base import ARCH_NAMES, get_config, valid_cells  # noqa: E402
from repro.core.modes import peaks_for  # noqa: E402

ARCH = ARCH_NAMES[0]
CELL = valid_cells(get_config(ARCH))[0]
V5E = "TPU v5 lite"


class TestCellRoofline:
    def test_analytic_terms_without_dryrun(self, monkeypatch, tmp_path):
        monkeypatch.setattr(roofline, "DRYRUN_DIR", tmp_path)
        row = cell_row = roofline.cell_roofline(ARCH, CELL, device_kind=V5E)
        assert row["arch"] == ARCH and row["cell"] == CELL
        assert row["compute_s"] > 0 and row["memory_s"] > 0
        assert row["collective_s"] == 0          # no artifact, no bytes
        assert row["dominant"] in ("compute", "memory", "collective")
        assert 0 < row["roofline_fraction"] <= 1.0
        assert 0 < row["useful_ratio"] <= 1.0
        assert row["hlo_flops_reported"] is None
        assert row["peak_gib"] is None
        # compute term is exactly analytic FLOPs over the pod peak
        assert cell_row["compute_s"] == pytest.approx(
            row["analytic_flops"] / 256 / peaks_for(V5E).flops_bf16)

    def test_merges_dryrun_artifact(self, monkeypatch, tmp_path):
        monkeypatch.setattr(roofline, "DRYRUN_DIR", tmp_path)
        artifact = {
            "collective_bytes": {"all-reduce": 10 ** 9,
                                 "all-gather": 5 * 10 ** 8},
            "cost": {"flops": 1.0e15, "bytes_accessed": 2.0e12},
            "memory": {"peak_bytes": 8 * 2 ** 30},
        }
        (tmp_path / f"{ARCH}__{CELL}__16x16.json").write_text(
            json.dumps(artifact))
        row = roofline.cell_roofline(ARCH, CELL, device_kind=V5E)
        assert row["collective_s"] == pytest.approx(
            1.5e9 / peaks_for(V5E).ici_bw)
        assert row["hlo_flops_reported"] == 1.0e15
        assert row["hlo_bytes_reported"] == 2.0e12
        assert row["peak_gib"] == pytest.approx(8.0)
        assert row["collective_detail"] == artifact["collective_bytes"]

    def test_mesh_tag_scales_device_count(self, monkeypatch, tmp_path):
        monkeypatch.setattr(roofline, "DRYRUN_DIR", tmp_path)
        small = roofline.cell_roofline(ARCH, CELL, "16x16", device_kind=V5E)
        big = roofline.cell_roofline(ARCH, CELL, "2x16x16", device_kind=V5E)
        assert big["compute_s"] == pytest.approx(small["compute_s"] / 2)

    def test_unknown_device_kind_raises(self, monkeypatch, tmp_path):
        monkeypatch.setattr(roofline, "DRYRUN_DIR", tmp_path)
        with pytest.raises(KeyError, match="no published peaks"):
            roofline.cell_roofline(ARCH, CELL, device_kind="cpu")


class TestFmtS:
    def test_ranges(self):
        assert roofline.fmt_s(2.5).strip() == "2.50s"
        assert roofline.fmt_s(0.0052).strip() == "5.20ms"
        assert roofline.fmt_s(1.5e-5).strip() == "15.0us"


class TestPrintTable:
    @pytest.fixture
    def rows(self, monkeypatch, tmp_path):
        monkeypatch.setattr(roofline, "DRYRUN_DIR", tmp_path)
        return [roofline.cell_roofline(ARCH, CELL, device_kind=V5E)]

    def test_plain(self, rows, capsys):
        roofline.print_table(rows)
        out = capsys.readouterr().out
        assert ARCH in out and CELL in out
        assert "dominant" in out
        assert "-" in out                        # missing peakGiB placeholder

    def test_markdown(self, rows, capsys):
        roofline.print_table(rows, md=True)
        lines = capsys.readouterr().out.splitlines()
        assert lines[0].startswith("| arch |")
        assert lines[1].startswith("|---|")
        assert all(ln.startswith("|") for ln in lines)


class TestAllRows:
    def test_covers_every_valid_cell(self, monkeypatch, tmp_path):
        monkeypatch.setattr(roofline, "DRYRUN_DIR", tmp_path)
        rows = roofline.all_rows(device_kind=V5E)
        expected = sum(len(valid_cells(get_config(a))) for a in ARCH_NAMES)
        assert len(rows) == expected
        assert {r["arch"] for r in rows} == set(ARCH_NAMES)
