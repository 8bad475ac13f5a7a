"""The phase and span reductions (`phases.py`) on a slice written by hand."""
import pytest

from bench import phases
from bench import trace as xt

CYC = "jit(_deflated_cycle)"
MATVEC = f"{CYC}/vmap(skr/arnoldi)/while/body/skr/arnoldi/matvec/mul"
ORTHOG = f"{CYC}/vmap(skr/arnoldi)/while/body/skr/arnoldi/orthog/dot_general"
LOOP = f"{CYC}/vmap(skr/arnoldi)/while"
SELECT = f"{CYC}/vmap(skr/arnoldi)/while/body/select_n"
RITZ = f"{CYC}/skr/ritz/cond/branch_1_fun/svd"
LSTSQ = f"{CYC}/skr/lstsq/jit(qr)/householder_product"
UPDATE = f"{CYC}/skr/update/dot_general"
GATHER = "jit(take)/gather"


def _scoped():
    # slice [100, 200) ns, one chip: a loop [100,150) holding a matvec
    # [105,115), an orthog [115,135) and a select [135,140); then lstsq
    # [150,160), ritz [160,170), an update [175,180) and an unscoped
    # gather [190,200). Idle: [170,175), [180,190)
    chip = [("while.1", 100, 150, LOOP), ("fusion.2", 105, 115, MATVEC),
            ("fusion.3", 115, 135, ORTHOG), ("fusion.4", 135, 140, SELECT),
            ("custom-call.5", 150, 160, LSTSQ), ("fusion.6", 160, 170, RITZ),
            ("fusion.7", 175, 180, UPDATE), ("gather.8", 190, 200, GATHER)]
    host = [("skr:execute_row", 90, 210), ("skr:solve_batch", 95, 188),
            ("np.asarray", 168, 178),
            ("skr:host_sync.cycle_flags", 165, 178),
            ("skr:cycle_dispatch", 178, 179),
            ("skr:host_sync.finalize", 182, 184),
            ("prepare_row", 186, 189)]
    tr = xt.Trace(slice=(100, 200), ops=[[c[:3] for c in chip]], host=host)
    return tr, [chip]


def test_phase_of_an_op_name():
    assert phases.phase(MATVEC) == "matvec"
    assert phases.phase(ORTHOG) == "orthog"
    assert phases.phase(LOOP) == "basis"
    assert phases.phase(SELECT) == "basis"
    assert phases.phase(RITZ) == "ritz"
    assert phases.phase(LSTSQ) == "lstsq"
    assert phases.phase(UPDATE) == "update"
    assert phases.phase("jit(_entry)/skr/entry/vmap(vmap())/mul") == "entry"
    assert phases.phase("jit(_from_z_b)/skr/finalize/vmap()/mul") == \
        "finalize"
    assert phases.phase(GATHER) == "unscoped"
    assert phases.phase("") == "unscoped"
    # a root with no phase under it, and fused names (the first counts)
    assert phases.phase("jit(f)/skr/mul") == "unscoped"
    assert phases.phase(f"{MATVEC};{UPDATE}") == "matvec"
    # as a TPU profile writes them
    assert phases.phase(f"{CYC}/skr/lstsq:") == "lstsq"
    assert phases.phase(f"{ORTHOG}:") == "orthog"


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 50000 }
    events { metadata_id: 2 offset_ps: 5000 duration_ps: 10000 }
    events { metadata_id: 4 offset_ps: 20000 duration_ps: 5000 }
    events { metadata_id: 3 offset_ps: 60000 duration_ps: 4000 }
    events { metadata_id: 4 offset_ps: 66000 duration_ps: 2000 } }
  event_metadata { key: 1 value { id: 1 name: "while.1"
    stats { metadata_id: 8 str_value: "%s" } } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2"
    stats { metadata_id: 7 str_value: "%%fusion.2 = f32[8] fusion()" }
    stats { metadata_id: 8 ref_value: 9 } } }
  event_metadata { key: 3 value { id: 3 name: "gather.3"
    stats { metadata_id: 8 str_value: "%s" } } }
  event_metadata { key: 4 value { id: 4 name: "copy.4" } }
  stat_metadata { key: 7 value { id: 7 name: "long_name" } }
  stat_metadata { key: 8 value { id: 8 name: "tf_op" } }
  stat_metadata { key: 9 value { id: 9 name: "%s" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 990
    events { metadata_id: 1 offset_ps: 0 duration_ps: 80000 } }
  event_metadata { key: 1 value { id: 1 name: "%s" } }
}
""" % (LOOP, GATHER, MATVEC, xt.SLICE)


def test_read_op_names_from_event_metadata(tmp_path):
    """The op name is a stat of the event's metadata, held as a string or
    as a reference to an interned one; times agree with trace.read. An op
    with none takes the phase of the op it runs in, or is `inserted`."""
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    chip, = phases.read(str(path))
    assert chip == [("while.1", 1000, 1050, LOOP),
                    ("fusion.2", 1005, 1015, MATVEC),
                    ("copy.4", 1020, 1025, ""),
                    ("gather.3", 1060, 1064, GATHER),
                    ("copy.4", 1066, 1068, "")]
    tr = xt.read(str(path))
    assert tr.slice == (990, 1070)
    assert [op[:3] for op in chip] == tr.ops[0]
    p = phases.reduce(tr, [chip])
    # the loop's copy is basis, the top-level one inserted
    assert p.phase_s == pytest.approx({"basis": 40e-9, "matvec": 10e-9,
                                       "unscoped": 4e-9, "inserted": 2e-9})


def test_device_time_by_phase():
    tr, ops = _scoped()
    p = phases.reduce(tr, ops)
    assert p.busy_s == pytest.approx(85e-9)
    # the loop's own time leaves out the three ops nested in it
    assert p.phase_s == pytest.approx({
        "basis": 15e-9 + 5e-9, "matvec": 10e-9, "orthog": 20e-9,
        "lstsq": 10e-9, "ritz": 10e-9, "update": 5e-9, "unscoped": 10e-9})
    assert p.scoped
    assert p.share("matvec") == pytest.approx(100 * 10 / 85)
    shares = [p.share(n) for n in ("matvec", "orthog", "basis", "unscoped")]
    shares.append(p.share("lstsq", "ritz"))
    rest = p.share("entry", "update", "finalize")
    assert sum(shares) + rest == pytest.approx(100.0)


def test_idle_gaps_by_program_span():
    tr, ops = _scoped()
    p = phases.reduce(tr, ops)
    # [170,175): midpoint 172.5 under cycle_flags (JAX's np.asarray inside
    # it does not count); [180,190): midpoint 185 in solve_batch's own
    # time, past the finalize fetch
    assert p.gaps_s == pytest.approx({"host_sync.cycle_flags": 5e-9,
                                      "solve_batch": 10e-9})
    assert p.idle_share(*phases.CYCLE_SPANS) == pytest.approx(5.0)
    assert p.idle_share(*phases.ROW_SPANS) == pytest.approx(10.0)
    # the old table keeps its rule: the innermost annotation of any kind
    assert xt.summarize(tr).gaps_s == pytest.approx(
        {"np.asarray": 5e-9, "skr:solve_batch": 10e-9})


def test_no_program_spans_or_scopes_reads_nothing():
    tr, ops = _scoped()
    bare = xt.Trace(slice=tr.slice, ops=tr.ops,
                    host=[h for h in tr.host if not h[0].startswith("skr:")])
    unscoped = [[(n, s, e, GATHER) for n, s, e, _ in ops[0]]]
    p = phases.reduce(bare, unscoped)
    assert not p.scoped
    assert set(p.gaps_s) == {phases.NONE}


def test_span_self_times_by_label():
    ev = [dict(name="execute_row", ts=0, dur=100),
          dict(name="solve_batch", ts=10, dur=80),
          dict(name="host_sync", ts=12, dur=3, args={"what": "entry_flags"}),
          dict(name="cycle_dispatch", ts=20, dur=5),
          dict(name="host_sync", ts=25, dur=30, args={"what": "cycle_flags"}),
          dict(name="host_sync", ts=80, dur=5, args={"what": "finalize"})]
    own = phases.self_times(ev, 0, 95)
    assert own == pytest.approx({k: v / 1e9 for k, v in {
        "execute_row": 100 - 80 - 5, "solve_batch": 80 - 3 - 5 - 30 - 5,
        "host_sync.entry_flags": 3, "cycle_dispatch": 5,
        "host_sync.cycle_flags": 30, "host_sync.finalize": 5}.items()})


def test_spans_open_at_the_profilers_start_and_stop_come_from_the_tracer():
    """The profile holds no `skr:` annotation for a span that was open when
    the profiler started or stopped; the tracer's own record of it, moved
    onto the profile's clock by the offset at which the recorded spans
    line up, takes its place."""
    tr, _ = _scoped()
    # what a profile holds: neither execute_row (open at the start) nor
    # solve_batch (open at the stop, which falls inside it here)
    host = [h for h in tr.host
            if h[0] not in ("skr:execute_row", "skr:solve_batch")]
    profiled = xt.Trace(slice=tr.slice, ops=tr.ops, host=host)
    shift = 10**6                    # the tracer's clock runs behind
    events = [("execute_row", 90 - shift, 270 - shift),
              ("solve_batch", 150 - shift, 260 - shift),
              ("host_sync.cycle_flags", 165 - shift, 178 - shift),
              ("cycle_dispatch", 178 - shift, 179 - shift),
              ("host_sync.finalize", 182 - shift, 184 - shift),
              # an earlier row's spans, which line up with nothing
              ("host_sync.cycle_flags", 10 - shift, 20 - shift)]
    assert phases._clock_offset(phases.program_spans(profiled), events) \
        == shift
    assert sorted(phases.program_spans(profiled, events)) == [
        ("cycle_dispatch", 178, 179), ("execute_row", 90, 270),
        ("host_sync.cycle_flags", 165, 178),
        ("host_sync.finalize", 182, 184), ("solve_batch", 150, 260)]
    # [180,190): midpoint 185 in solve_batch's own time, recovered
    assert phases.gaps_by_span(profiled, events) == pytest.approx(
        {"host_sync.cycle_flags": 5e-9, "solve_batch": 10e-9})
    # without the tracer that gap has no program span
    assert phases.gaps_by_span(profiled) == pytest.approx(
        {"host_sync.cycle_flags": 5e-9, phases.NONE: 10e-9})
