"""The scoped reading of a profile: device time by plan step, the
complex <-> f32-plane glue, and the runtime's relayout work inside the
copy spans; on hand-made events, on a hand-made profile file found the
way a traced run leaves it, and on one call recorded on a v5e."""
import json
import pathlib
import types

import numpy as np
import pytest

from sarbench import scopes as S, spec, trace as T

DATA = pathlib.Path(__file__).resolve().parent / "data"
CHIP = "/device:TPU:0"


def test_scope_paths_steps_and_glue():
    assert S.scope_path("jit(focus_fused3)/azimuth_fft/split/real") == \
        ("focus_fused3", ("azimuth_fft", "split"))
    assert S.scope_path("jit(focus_fused3)/range_comp_rcmc/jit(spectral_op)"
                        "/spectral_axis1/while/body/add")[1][:2] == \
        ("range_comp_rcmc", "jit(spectral_op)")
    assert S.scope_path("jit(focus_fused3)/range_comp_rcmc/jit(spectral_op)"
                        "/spectral_axis1/pallas_call:") == \
        ("focus_fused3", ("range_comp_rcmc", "jit(spectral_op)",
                          "spectral_axis1"))
    assert S.scope_path("raw") == (None, ("raw",))
    assert S.step_of("raw:") == S.ARGUMENT
    assert S.scope_path(None) == S.scope_path("") == (None, ())
    assert S.step_of("jit(focus_fused3)/azimuth_fft/unsplit/add") == \
        "azimuth_fft"
    assert S.step_of("jit(f)/add") == T.NO_SPAN        # no step scope
    assert S.step_of(None) == T.NO_SPAN
    assert S.step_of("raw") == S.ARGUMENT
    assert S.is_glue("jit(focus_fused3)/azimuth_compression/unsplit/add")
    assert S.is_glue("raw")            # the compiler's split of the argument
    assert not S.is_glue("jit(focus_fused3)/azimuth_fft/jit(spectral_op)")
    # a primitive called split is no scope
    assert not S.is_glue("jit(focus_fused3)/azimuth_fft/split")
    assert not S.is_glue(None)


def hand_scoped():
    # window 0..100 ns: the argument split, three steps with their glue, an
    # op with no metadata, and a kernel that runs past the window's end
    named = {CHIP: [
        ("raw", 0, 4),
        ("jit(focus_fused3)/azimuth_fft/jit(spectral_op)", 4, 20),
        ("jit(focus_fused3)/azimuth_fft/unsplit/add", 20, 24),
        ("jit(focus_fused3)/range_comp_rcmc/jit(spectral_op)", 22, 40),
        ("jit(focus_fused3)/azimuth_compression/unsplit/add", 40, 46),
        (None, 46, 47),
        ("jit(focus_fused3)/azimuth_compression/jit(spectral_op)", 90, 130),
    ]}
    ops = {c: [("op", s, e) for _, s, e in evs] for c, evs in named.items()}
    spans = [("h2d", 0, 20), ("dispatch", 20, 50), ("d2h", 50, 90)]
    host = [  # two threads linearize at once; three transpose, one past d2h
        ("Linearize", "a", 5, 15), ("Linearize", "b", 10, 18),
        ("Transpose::ExecuteChunk", "a", 40, 60),
        ("Transpose::ExecuteChunk", "b", 55, 70),
        ("Transpose::ExecuteChunk", "c", 65, 95),
        ("tpu::System::TransferFromDevice", "d", 45, 50)]
    return S.ScopedTrace(ops, spans, 0, 100, named=named, host=host)


def test_device_time_by_step_and_glue_by_hand():
    t = hand_scoped()
    assert t.busy_seconds() == pytest.approx(57e-9)
    by = t.seconds_by_step()
    assert by == {"azimuth_fft": pytest.approx(20e-9),
                  "range_comp_rcmc": pytest.approx(18e-9),
                  "azimuth_compression": pytest.approx(16e-9),
                  S.ARGUMENT: pytest.approx(4e-9),
                  T.NO_SPAN: pytest.approx(1e-9)}
    assert list(by)[0] == "azimuth_fft"
    # raw 0-4, unsplit 20-24 (overlapped by a kernel: counted once), 40-46
    assert t.glue_seconds() == pytest.approx(14e-9)
    assert t.names_steps() and t.programs() == {"focus_fused3"}


def test_relayout_union_over_threads_inside_two_spans_by_hand():
    t = hand_scoped()
    # h2d 0-20: Linearize 5-15 and 10-18 on two threads make 13 ns
    assert t.host_share(S.RELAYOUT, ["h2d"]) == pytest.approx(13 / 20)
    # d2h 50-90: transposes 40-95 over three threads cover all of it
    assert t.host_share(S.RELAYOUT, ["d2h"]) == pytest.approx(1.0)
    assert t.host_share(S.RELAYOUT, S.COPY_SPANS) == pytest.approx(53 / 60)
    assert t.host_share(S.TRANSFER, S.COPY_SPANS) == 0.0    # 45-50: dispatch
    assert t.host_share(S.RELAYOUT, ["none such"]) == 0.0
    assert t.has_host_events(S.TRANSFER)
    assert not S.ScopedTrace({}, [], 0, 1).has_host_events(S.RELAYOUT)


def test_a_program_without_step_names_names_no_steps():
    t = S.ScopedTrace({CHIP: [("add", 0, 5)]}, [], 0, 10,
                      named={CHIP: [("jit(f)/add", 0, 5)]})
    assert not t.names_steps()
    assert t.seconds_by_step() == {T.NO_SPAN: pytest.approx(5e-9)}
    untraced = types.SimpleNamespace(trace=None)
    assert S.for_run(untraced) is None
    assert spec.load_reader("complex_glue_share.batch")(untraced) is None


# -- a profile file, found the way a traced run leaves it ---------------------

XSPACE = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000 }
    events { metadata_id: 2 offset_ps: 4000 duration_ps: 16000 }
    events { metadata_id: 3 offset_ps: 20000 duration_ps: 4000
             stats { metadata_id: 1 ref_value: 9 } }
    events { metadata_id: 4 offset_ps: 24000 duration_ps: 1000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 5 offset_ps: 0 duration_ps: 24000 } }
  event_metadata { key: 1 value { id: 1 name: "custom-call.1"
    stats { metadata_id: 1 str_value: "raw" } } }
  event_metadata { key: 2 value { id: 2 name: "spectral_axis0.2"
    stats { metadata_id: 1
            str_value: "jit(focus_fused3)/azimuth_fft/jit(spectral_op)" } } }
  event_metadata { key: 3 value { id: 3 name: "multiply_add_fusion" } }
  event_metadata { key: 4 value { id: 4 name: "copy-done" } }
  event_metadata { key: 5 value { id: 5 name: "jit_focus_fused3" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 9 value { id: 9
                  name: "jit(focus_fused3)/azimuth_fft/unsplit/add" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 10 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 20000 }
    events { metadata_id: 3 offset_ps: 50000 duration_ps: 40000 } }
  lines { id: 11 name: "tpu_worker_0" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 5000 duration_ps: 10000 }
    events { metadata_id: 5 offset_ps: 50000 duration_ps: 30000 } }
  lines { id: 12 name: "tpu_worker_1" timestamp_ns: 1000
    events { metadata_id: 5 offset_ps: 70000 duration_ps: 30000 }
    events { metadata_id: 6 offset_ps: 45000 duration_ps: 5000 } }
  lines { id: 13 name: "pjrt-tpu-tasks" timestamp_ns: 1000
    events { metadata_id: 7 offset_ps: 15000 duration_ps: 4000 }
    events { metadata_id: 8 offset_ps: 15000 duration_ps: 4000 } }
  event_metadata { key: 1 value { id: 1 name: "window" } }
  event_metadata { key: 2 value { id: 2 name: "h2d" } }
  event_metadata { key: 3 value { id: 3 name: "d2h" } }
  event_metadata { key: 4 value { id: 4 name: "Linearize" } }
  event_metadata { key: 5 value { id: 5 name: "Transpose::ExecuteChunk" } }
  event_metadata { key: 6 value { id: 6
                   name: "tpu::System::TransferFromDevice" } }
  event_metadata { key: 7 value { id: 7
                   name: "X64FromTuple c64[4,4096,4096]{2,1,0}" } }
  event_metadata { key: 8 value { id: 8 name: "Transpose" } }
}
'''


def write_profile(tmp_path, monkeypatch, text=XSPACE):
    """The profile where ``trace.recording`` leaves it, under TMPDIR."""
    import tempfile

    from jax.profiler import ProfileData

    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    d = tmp_path / "sarbench_trace_x" / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    path = d / "h.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return path


@pytest.fixture
def recorded_file(tmp_path, monkeypatch):
    return write_profile(tmp_path, monkeypatch)


def same_as_base(path):
    """The one walk of a profile file, checked against the reading through
    ``jax.profiler.ProfileData``."""
    from sarbench.harness import SPAN_NAMES

    base = T.Trace.from_xplane(str(path), SPAN_NAMES)
    t = S.ScopedTrace.from_xplane(str(path), SPAN_NAMES)
    assert (t.ops, t.spans, t.start_ns, t.end_ns) == \
        (base.ops, base.spans, base.start_ns, base.end_ns)
    assert t.breakdown() == base.breakdown()
    assert (t.busy_seconds(), t.idle_share()) == \
        (base.busy_seconds(), base.idle_share())
    return t


def test_profile_file_keeps_the_base_reading_and_adds_names(
        recorded_file, tmp_path, monkeypatch):
    t = same_as_base(recorded_file)
    # op names from the event metadata, from an event's own stat, interned
    assert t.named[CHIP] == [
        ("raw", 1000, 1004),
        ("jit(focus_fused3)/azimuth_fft/jit(spectral_op)", 1004, 1020),
        ("jit(focus_fused3)/azimuth_fft/unsplit/add", 1020, 1024),
        (None, 1024, 1025)]
    assert [s for _, s, _ in t.named[CHIP]] == [s for _, s, _ in t.ops[CHIP]]
    # kinds by the name's first word; other events are not kept
    assert sorted(t.host) == [
        ("Linearize", "tpu_worker_0", 1005, 1015),
        ("Transpose::ExecuteChunk", "tpu_worker_0", 1050, 1080),
        ("Transpose::ExecuteChunk", "tpu_worker_1", 1070, 1100),
        ("X64FromTuple", "pjrt-tpu-tasks", 1015, 1019),
        ("tpu::System::TransferFromDevice", "tpu_worker_1", 1045, 1050)]
    assert t.glue_seconds() == pytest.approx(8e-9)
    assert t.seconds_by_step()["azimuth_fft"] == pytest.approx(20e-9)
    # the call recorded on a v5e, as a profile file: the same reading as
    # the base's, and every scoped reading equal to the last digit
    kept, _ = recorded_call()
    t = same_as_base(write_profile(tmp_path / "call", monkeypatch,
                                   recorded_call_xspace()))
    assert (t.ops, t.spans, t.start_ns, t.end_ns, t.named) == \
        (kept.ops, kept.spans, kept.start_ns, kept.end_ns, kept.named)
    assert sorted(t.host) == sorted(kept.host)
    assert t.breakdown() == kept.breakdown()
    assert t.seconds_by_step() == kept.seconds_by_step()
    assert t.glue_seconds() == kept.glue_seconds()
    assert t.host_share(S.RELAYOUT, S.COPY_SPANS) == \
        kept.host_share(S.RELAYOUT, S.COPY_SPANS)


def test_a_traced_run_keeps_its_own_reading(recorded_file, monkeypatch):
    """A run whose trace is already scoped is read as it is: no profile is
    looked for under TMPDIR, nor parsed again."""
    from sarbench.harness import SPAN_NAMES

    t = S.ScopedTrace.from_xplane(str(recorded_file), SPAN_NAMES)

    def no_search(*args, **kw):
        raise AssertionError("searched for a profile")
    monkeypatch.setattr(S.glob, "glob", no_search)
    monkeypatch.setattr(S, "_load", no_search)
    run = types.SimpleNamespace(trace=t)
    assert S.for_run(run) is t
    assert spec.load_reader("complex_glue_share.batch")(run) == \
        pytest.approx(100 * 8 / 25)


def test_readers_find_the_run_by_its_window(recorded_file):
    trace = T.Trace({}, [], 1000, 1100)
    run = types.SimpleNamespace(trace=trace)
    assert S.for_run(run) is not None
    glue = spec.load_reader("complex_glue_share.batch")(run)
    assert glue == pytest.approx(100 * 8 / 25)
    relayout = spec.load_reader("transfer_relayout_share.batch")(run)
    # h2d 1000-1020: 1005-1019; d2h 1050-1090: all of it
    assert relayout == pytest.approx(100 * (14 + 40) / (20 + 40))
    other = types.SimpleNamespace(trace=T.Trace({}, [], 1000, 1099))
    assert S.for_run(other) is None
    assert spec.load_reader("transfer_relayout_share.batch")(other) is None


def test_a_program_without_step_names_reads_no_glue(tmp_path, monkeypatch):
    """As the program before it named its steps: the glue share goes
    silent, the runtime's relayout share still reads."""
    write_profile(tmp_path, monkeypatch,
                  XSPACE.replace("jit(focus_fused3)", "jit(f)"))
    run = types.SimpleNamespace(trace=T.Trace({}, [], 1000, 1100))
    assert spec.load_reader("complex_glue_share.batch")(run) is None
    assert spec.load_reader("transfer_relayout_share.batch")(run) == \
        pytest.approx(100 * 54 / 60)


# -- one call recorded on a v5e -----------------------------------------------

def recorded_call():
    doc = json.loads((DATA / "tpu_v5e_batch4_scoped_call.json").read_text())
    ops = {CHIP: [(n, s, e) for n, _, s, e in doc["ops"]]}
    named = {CHIP: [(o, s, e) for _, o, s, e in doc["ops"]]}
    t = S.ScopedTrace(ops, [tuple(s) for s in doc["spans"]], *doc["window"],
                      named=named, host=[tuple(h) for h in doc["host"]])
    return t, doc


def recorded_call_xspace():
    """The recorded call as the text of a profile it could come from: one
    event metadata entry per operation, named as a v5e names it, with its
    ``op_name`` in the ``tf_op`` stat; the host spans and copy work on
    their threads, with the ``window`` span around them."""
    _, doc = recorded_call()

    def event(key, s, e):
        # with sub-ns parts, which whole ns drop
        return (f"events {{ metadata_id: {key} "
                f"offset_ps: {int(s) * 1000 + 999} "
                f"duration_ps: {(int(e) - int(s)) * 1000 + 1} }}")

    ops, op_md = [], []
    for key, (n, o, s, e) in enumerate(doc["ops"], 1):
        ops.append(event(key, s, e))
        stat = "" if o is None else \
            f"stats {{ metadata_id: 1 str_value: {json.dumps(o)} }}"
        name = f"%{n}.{key} = f32[4,4096,4096]{{2,1,0}} {n}()"
        op_md.append(f"event_metadata {{ key: {key} value {{ id: {key} "
                     f"name: {json.dumps(name)} {stat} }} }}")
    threads: dict = {"python": [event(1, *doc["window"])]}
    names = ["window"]
    for n, s, e in doc["spans"]:
        names.append(n)
        threads["python"].append(event(len(names), s, e))
    for k, thread, s, e in doc["host"]:
        names.append(k + (" c64[4,4096,4096]{2,1,0}"
                          if k == "X64FromTuple" else ""))
        threads.setdefault(thread, []).append(event(len(names), s, e))
    lines = [f"lines {{ name: {json.dumps(t)} timestamp_ns: 0 "
             f"{' '.join(evs)} }}" for t, evs in threads.items()]
    host_md = [f"event_metadata {{ key: {k} value {{ id: {k} "
               f"name: {json.dumps(n)} }} }}" for k, n in enumerate(names, 1)]
    return (f'planes {{ id: 1 name: "{CHIP}" '
            f'lines {{ name: "{T.OPS_LINE}" timestamp_ns: 0 {" ".join(ops)} }}'
            f' {" ".join(op_md)} '
            f'stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }} }}\n'
            f'planes {{ id: 2 name: "{T.HOST_PLANE}" {" ".join(lines)} '
            f'{" ".join(host_md)} }}\n')


def grid(doc, intervals):
    """1 us cells of the window covered by any of ``intervals``."""
    t0, t1 = doc["window"]
    centres = np.arange(t0, t1, 1000.0) + 500.0
    hit = np.zeros(centres.size, bool)
    for s, e in intervals:
        hit |= (centres >= s) & (centres < e)
    return centres, hit


def test_recorded_call_names_every_operation():
    """Each plan step owns its kernel and its glue; what no scope reaches
    is the compiler's split of the argument (``raw``) and the copies of
    constants, which carry no name."""
    t, doc = recorded_call()
    assert t.programs() == {"focus_fused3"}
    by = t.seconds_by_step()
    assert list(by) == ["range_comp_rcmc", "azimuth_compression",
                        "azimuth_fft", S.ARGUMENT, T.NO_SPAN]
    assert sum(by.values()) == pytest.approx(t.busy_seconds(), rel=1e-6)
    assert by[T.NO_SPAN] < 1e-4 * t.busy_seconds()
    steps = [(n, S.step_of(o)) for n, o, _, _ in doc["ops"]
             if not n.startswith("copy-")]
    assert steps == [
        ("custom-call", S.ARGUMENT), ("custom-call", S.ARGUMENT),
        ("spectral_axis0", "azimuth_fft"),
        ("multiply_add_fusion", "azimuth_fft"),
        ("spectral_axis1", "range_comp_rcmc"),
        ("multiply_add_fusion", "range_comp_rcmc"),
        ("spectral_axis0", "azimuth_compression"),
        ("multiply_add_fusion", "azimuth_compression"),
        ("custom-call", "azimuth_compression")]


def test_recorded_call_glue_against_a_brute_force_grid():
    t, doc = recorded_call()
    _, busy = grid(doc, [(s, e) for _, _, s, e in doc["ops"]])
    _, glue = grid(doc, [(s, e) for _, o, s, e in doc["ops"]
                         if S.is_glue(o)])
    assert t.glue_seconds() == pytest.approx(glue.sum() * 1e-6, rel=1e-3)
    share = t.glue_seconds() / t.busy_seconds()
    assert share == pytest.approx(glue.sum() / busy.sum(), rel=1e-3)
    read = spec.load_reader("complex_glue_share.batch")
    assert read(types.SimpleNamespace(trace=t)) == \
        pytest.approx(100 * share)
    assert 20 < 100 * share < 30


def test_recorded_call_relayout_against_a_brute_force_grid():
    """On the way out, the copy's time is the join of the complex64's
    halves on one host thread, not the tiled-to-row-major transposes."""
    t, doc = recorded_call()
    centres, relayout = grid(doc, [(s, e) for k, _, s, e in doc["host"]
                                   if k in S.RELAYOUT])
    _, copies = grid(doc, [(s, e) for n, s, e in doc["spans"]
                           if n in S.COPY_SPANS])
    share = t.host_share(S.RELAYOUT, S.COPY_SPANS)
    assert share == pytest.approx((relayout & copies).sum() / copies.sum(),
                                  rel=1e-3)
    read = spec.load_reader("transfer_relayout_share.batch")
    assert read(types.SimpleNamespace(trace=t)) == pytest.approx(100 * share)
    assert share > 0.9
    assert t.host_share(["X64FromTuple"], ["d2h"]) > 0.9
    assert t.host_share(["Transpose::ExecuteChunk"], ["d2h"]) < 0.1
    assert t.host_share(["Linearize"], ["h2d"]) > 0.8
