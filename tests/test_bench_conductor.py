"""tools/bench_conductor.py: the one-command r06 sweep conductor.

Pins the pieces the TPU window will lean on blind:

  * check_schema accepts BOTH bench-JSON generations — the historical
    driver wrappers (the shapes of the round 1-5 records, including r01's
    rc=1/parsed=null crash record; the records themselves left the tree
    in PR 24) and the conductor's own mtpu-bench1 docs — and rejects
    actual garbage;
  * verdict math (promote/regress/neutral thresholds, the smoke and
    no-prior escape hatches);
  * prior_reading across both document shapes;
  * find_prior picks the NEWEST round and never diffs a file against
    itself;
  * (slow) one real --smoke lever end to end: subprocess, schema-valid
    output JSON, a verdict line, and the notes skeleton.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import bench_conductor as bc  # noqa: E402


# ------------------------------------------------------------ check_schema

def test_check_schema_accepts_driver_wrapper_history(tmp_path):
    """The two shapes the driver wrote in rounds 1-5: a crash record
    (r01: rc=1, no parsed payload) and a parsed reading (r05)."""
    cmd = "if [ -f bench.py ]; then python bench.py; else exit 0; fi"
    docs = {
        "BENCH_r01.json": {"n": 1, "cmd": cmd, "rc": 1,
                           "tail": "Traceback ...", "parsed": None},
        "BENCH_r05.json": {"n": 5, "cmd": cmd, "rc": 0, "tail": "",
                           "parsed": {
                               "metric": "LLFF 384x256 N=32 train "
                                         "images/sec (1 chip, bf16, "
                                         "ResNet-50)",
                               "value": 18.154, "unit": "images/sec",
                               "vs_baseline": 4.538,
                               "vs_baseline_range": [3.026, 9.077],
                               "vs_reference_flops_ceiling": 1.635,
                               "best_config": "flagship_b4",
                               "variants": {"flagship_b4": 18.154}}},
    }
    paths = []
    for name, doc in docs.items():
        (tmp_path / name).write_text(json.dumps(doc))
        paths.append(str(tmp_path / name))
    assert bc.check_schema(paths) == []


def test_check_schema_accepts_conductor_doc(tmp_path):
    doc = {"schema": bc.SCHEMA, "round": "r99", "smoke": True,
           "prior": None,
           "levers": {"realloop_b4": {
               "cmd": "python bench.py", "rc": 0,
               "parsed": {"variants": {"realloop_b4": 1.0}, "value": 1.0},
               "reading": 1.0, "prior": None, "verdict": "neutral",
               "note": "no prior reading"}}}
    p = tmp_path / "BENCH_r99.json"
    p.write_text(json.dumps(doc))
    assert bc.check_schema([str(p)]) == []


def test_check_schema_rejects_garbage(tmp_path):
    bad = [("notjson.json", "{truncated"),
           ("list.json", "[1, 2]"),
           ("alien.json", json.dumps({"hello": "world"})),
           ("empty_levers.json", json.dumps({"schema": bc.SCHEMA,
                                             "levers": {}})),
           ("gutted_lever.json", json.dumps(
               {"schema": bc.SCHEMA,
                "levers": {"x": {"cmd": "c"}}})),
           ("bad_wrapper.json", json.dumps(
               {"rc": 0, "parsed": {"no_variants": 1}}))]
    for name, content in bad:
        p = tmp_path / name
        p.write_text(content)
        problems = bc.check_schema([str(p)])
        assert problems, f"{name} passed check_schema"
        assert name in problems[0]


# ----------------------------------------------------------------- verdicts

@pytest.mark.parametrize("reading,prior,smoke,want", [
    (1.0, None, False, "neutral"),    # no prior
    (100.0, 50.0, True, "neutral"),   # smoke never compares
    (None, 50.0, False, "regress"),   # errored with a prior on record
    (106.0, 100.0, False, "promote"),
    (94.0, 100.0, False, "regress"),
    (100.0, 100.0, False, "neutral"),
    (104.9, 100.0, False, "neutral"),
])
def test_judge_verdicts(reading, prior, smoke, want):
    verdict, note = bc.judge(reading, prior, smoke)
    assert verdict == want
    assert note


def test_prior_reading_both_shapes():
    wrapper = {"n": 3, "cmd": "x", "rc": 0, "tail": "",
               "parsed": {"value": 7.5,
                          "variants": {"realloop_b4": 7.5,
                                       "warppass_b4": "error: boom"}}}
    assert bc.prior_reading(wrapper, "realloop_b4") == 7.5
    assert bc.prior_reading(wrapper, "warppass_b4") is None  # error string
    # a lever the wrapper never measured takes NO prior from the headline
    # value (one wrapper = one bench run)
    assert bc.prior_reading(wrapper, "losspass_b4") is None
    # a crash record (r01 shape): parsed is null
    assert bc.prior_reading({"rc": 1, "parsed": None}, "realloop_b4") is None

    conductor = {"schema": bc.SCHEMA,
                 "levers": {"realloop_b4": {"reading": 9.25},
                            "losspass_b4": {"reading": None,
                                            "parsed": {"value": 3.0}}}}
    assert bc.prior_reading(conductor, "realloop_b4") == 9.25
    # falls through to the lever's own payload when reading is null
    assert bc.prior_reading(conductor, "losspass_b4") == 3.0
    assert bc.prior_reading(conductor, "serve_slo") is None
    assert bc.prior_reading(None, "realloop_b4") is None


def test_find_prior_picks_newest_and_skips_self(tmp_path):
    for n, payload in ((1, {"rc": 1, "parsed": None}),
                       (2, {"rc": 0, "parsed": {"value": 1.0,
                                                "variants": {}}})):
        (tmp_path / f"BENCH_r0{n}.json").write_text(json.dumps(payload))
    out = str(tmp_path / "BENCH_r03.json")
    path, doc = bc.find_prior(out, search_dir=str(tmp_path))
    assert os.path.basename(path) == "BENCH_r02.json"
    assert doc["rc"] == 0
    # writing over the newest round never diffs against itself
    path, _ = bc.find_prior(str(tmp_path / "BENCH_r02.json"),
                            search_dir=str(tmp_path))
    assert os.path.basename(path) == "BENCH_r01.json"
    path, doc = bc.find_prior(out, search_dir=str(tmp_path / "nowhere"))
    assert path is None and doc is None


def test_render_notes_one_section_per_lever():
    doc = {"round": "r06", "smoke": True,
           "levers": {"realloop_b4": {
               "reading": 1.5, "prior": None, "verdict": "neutral",
               "note": "no prior reading", "rc": 0, "tail": "last line"}}}
    text = bc.render_notes(doc, prior_path=None)
    assert "# BENCH_NOTES_r06" in text and "SMOKE" in text
    assert "## realloop_b4" in text
    assert "reading: 1.500" in text and "**neutral**" in text
    assert "decision: TODO promote / revert / hold" in text


def test_aot_coldstart_lever_aliases_serve_coldstart_variant(monkeypatch):
    """The r06 aot_coldstart lever runs the serve_coldstart bench variant:
    MINE_TPU_BENCH_VARIANTS must carry the VARIANT name (bench.py keys its
    payload on it) while the conductor record keeps the lever name."""
    lever = next(lv for lv in bc.LEVERS if lv["name"] == "aot_coldstart")
    assert lever["variant"] == "serve_coldstart"

    seen = {}

    def fake_run(cmd, env=None, **kw):
        seen["variants"] = env["MINE_TPU_BENCH_VARIANTS"]

        class P:
            returncode = 0
            stderr = ""
            stdout = json.dumps(
                {"value": 4.0, "variants": {"serve_coldstart": 4.0}})
        return P()

    monkeypatch.setattr(bc.subprocess, "run", fake_run)
    rec = bc.run_lever(lever, smoke=True, timeout_s=5.0)
    assert seen["variants"] == "serve_coldstart"
    assert rec["reading"] == 4.0  # read from the variant's payload entry


def test_stream_session_lever_in_sweep(monkeypatch):
    """The streaming-session cadence sweep rides the conductor: the lever
    keys the bench variant of the same name (no alias), and its knee-fps
    reading is attributed from the variant's own payload entry — never
    from another lever's headline value."""
    lever = next(lv for lv in bc.LEVERS if lv["name"] == "stream_session")
    assert lever.get("variant", lever["name"]) == "stream_session"

    seen = {}

    def fake_run(cmd, env=None, **kw):
        seen["variants"] = env["MINE_TPU_BENCH_VARIANTS"]

        class P:
            returncode = 0
            stderr = "  stream_session knee: K=8 (33.000 frames/s, ...)"
            stdout = json.dumps(
                {"value": 33.0, "variants": {"stream_session": 33.0}})
        return P()

    monkeypatch.setattr(bc.subprocess, "run", fake_run)
    rec = bc.run_lever(lever, smoke=True, timeout_s=5.0)
    assert seen["variants"] == "stream_session"
    assert rec["reading"] == 33.0

    # prior attribution: a wrapper that never measured stream_session
    # contributes NO prior, even with a numeric headline value
    wrapper = {"rc": 0, "parsed": {"value": 8.0,
                                   "variants": {"realloop_b4": 8.0}}}
    assert bc.prior_reading(wrapper, "stream_session") is None
    conductor = {"schema": bc.SCHEMA,
                 "levers": {"stream_session": {"reading": 31.5}}}
    assert bc.prior_reading(conductor, "stream_session") == 31.5


def test_main_rejects_unknown_lever(capsys):
    assert bc.main(["--levers", "nonsense"]) == 2
    assert "unknown lever" in capsys.readouterr().err


# ------------------------------------------- one real smoke lever (slow)

@pytest.mark.slow
def test_smoke_lever_end_to_end(tmp_path):
    """`--smoke --levers realloop_b4` through a real subprocess: exit 0,
    a verdict line on stdout, schema-valid consolidated JSON with a
    numeric smoke reading and a neutral verdict, and the notes skeleton."""
    out = str(tmp_path / "BENCH_rsmoke.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_conductor.py"),
         "--smoke", "--levers", "realloop_b4", "--round", "rsmoke",
         "--out", out],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "lever realloop_b4: reading=" in proc.stdout
    assert bc.check_schema([out]) == []
    with open(out) as f:
        doc = json.load(f)
    rec = doc["levers"]["realloop_b4"]
    assert doc["smoke"] is True and rec["rc"] == 0
    assert isinstance(rec["reading"], float) and rec["reading"] > 0
    assert rec["verdict"] == "neutral"  # smoke never compares to silicon
    assert rec["parsed"]["metric"].startswith("SMOKE")
    notes = tmp_path / "BENCH_NOTES_rsmoke.md"
    assert notes.exists() and "## realloop_b4" in notes.read_text()
