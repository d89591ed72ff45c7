"""The readers of the program's trace marks (``bench/lib/progtrace.py``),
on a hand-built trace with known answers, and its reading of a trace
file's event metadata."""
import importlib.util
import os

import pytest

from bench.lib import progtrace as P

MS = 1e6
PAGED = "jit(chunk_step)/while/body/closed_call/paged_kv/gather:"
QUANT = "jit(train_step)/transpose(jvp(als_potq))/abs:"
METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


def _op(name, opcode="fusion"):
    return f"%{name} = f32[8]{{0}} {opcode}(f32[8]{{0}} %p)"


def _spans():
    """(name, start_ms, end_ms, args): one call of two steps; the first
    admits request 7."""
    return [
        ("bench.window", 0, 100, {}), ("bench.serve_run", 0, 100, {}),
        ("serve.run", 1, 100, {"requests": 1}),
        ("serve.init_cache", 1, 5, {}),
        ("serve.step", 5, 50, {"step": 0}),
        ("serve.admit", 6, 20, {"uid": 7, "step": 0}),
        ("serve.dispatch", 20, 24, {"step": 0}),
        ("serve.overlap", 24, 26, {}), ("serve.wait", 26, 45, {}),
        ("serve.retire", 45, 48, {}), ("serve.check", 48, 49, {}),
        ("serve.step", 50, 95, {"step": 1}),
        ("serve.dispatch", 50, 54, {"step": 1}),
        ("serve.overlap", 54, 55, {}), ("serve.wait", 55, 90, {}),
        ("serve.retire", 90, 93, {}), ("serve.check", 93, 94, {}),
    ]


def _prog(spans=None, scoped=True):
    ops = [(_op("fusion.1"), 0, 3), (_op("while.1", "while"), 20, 30),
           (_op("fusion.2"), 22, 8), (_op("fusion.3"), 30, 10),
           (_op("copy.4", "copy"), 52, 33), (_op("fusion.5"), 85, 3)]
    names = {_op("fusion.1"): PAGED, _op("fusion.2"): PAGED,
             _op("fusion.3"): QUANT, _op("fusion.5"): PAGED,
             _op("while.1", "while"): PAGED} if scoped else {}
    spans = _spans() if spans is None else spans
    return P.from_events(
        {"/device:TPU:0": [(t, s * MS, d * MS) for t, s, d in ops]},
        [(n, s * MS, (e - s) * MS) for n, s, e, _ in spans],
        [a for *_, a in spans], names)


def _read(name, prog, monkeypatch):
    monkeypatch.setattr(P, "load", lambda path=None: prog)
    path = os.path.join(METRICS, f"{name}.py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read({})


def test_innermost_names_each_piece_by_the_deepest_span():
    spans = [("a", 0, 10), ("b", 2, 5), ("c", 3, 4), ("d", 5, 7),
             ("e", 12, 13)]
    assert P.innermost(spans) == [(0, 2, "a"), (2, 3, "b"), (3, 4, "c"),
                                  (4, 5, "b"), (5, 7, "d"), (7, 10, "a"),
                                  (12, 13, "e")]


def test_idle_is_put_down_to_the_span_the_host_was_in():
    # busy: [0,3) [22,40) [52,88) (the while is a container, left out);
    # idle: [3,22) [40,52) [88,100)
    idle = P.idle_by_span(_prog())
    want = {"serve.init_cache": 2, "serve.step": 3, "serve.admit": 14,
            "serve.dispatch": 4, "serve.wait": 7, "serve.retire": 6,
            "serve.check": 2, "serve.run": 5}
    assert set(idle) == set(want) | {"outside serve spans"}
    for k, v in want.items():
        assert idle[k] == pytest.approx(v * 1e-3), k
    assert idle["outside serve spans"] == pytest.approx(0)
    rep = P.report(_prog())
    assert rep["step_uncovered_share"] == pytest.approx(0.03)
    assert rep["serve_run_cover_min"] == pytest.approx(0.99)


def test_idle_outside_serve_spans_is_not_the_program_s():
    spans = [s for s in _spans() if s[0] != "serve.run"]
    idle = P.idle_by_span(_prog(spans))
    assert idle["outside serve spans"] == pytest.approx(5e-3)


def test_readers_on_a_hand_built_trace(monkeypatch, capsys):
    prog = _prog()
    # idle 43 ms of 100, 7 of it in serve.wait
    assert _read("idle_host.serve", prog, monkeypatch) == pytest.approx(36)
    assert "serve.admit" in capsys.readouterr().err
    # steps of 45 ms less waits of 19 and 35 ms
    assert _read("host_step_ms.serve", prog, monkeypatch) == \
        pytest.approx(18)
    # busy 57 ms: paged_kv [0,3) [22,30) [85,88), als_potq [30,40)
    assert _read("paged_kv_share.serve", prog, monkeypatch) == \
        pytest.approx(100 * 14 / 57)
    for name in ("quantize_share.serve", "quantize_share.train"):
        assert _read(name, prog, monkeypatch) == pytest.approx(100 * 10 / 57)


def test_readers_read_nothing_where_the_program_marks_nothing(monkeypatch):
    bare = _prog([s for s in _spans() if not s[0].startswith("serve.")],
                 scoped=False)
    for name in ("idle_host.serve", "host_step_ms.serve",
                 "paged_kv_share.serve", "quantize_share.serve",
                 "quantize_share.train"):
        assert _read(name, bare, monkeypatch) is None, name
        assert _read(name, None, monkeypatch) is None, name
    assert P.report(bare) == {}


def test_scopes_match_whole_names_under_transformations():
    assert P.in_scope(QUANT, "als_potq") and P.in_scope(PAGED, "paged_kv")
    assert not P.in_scope("jit(f)/als_potqx/mul:", "als_potq")
    assert not P.in_scope(PAGED, "als_potq")


def test_load_reads_op_names_and_span_arguments(tmp_path, monkeypatch):
    from jax.profiler import ProfileData

    ms = 1_000_000_000  # ps
    text = f'''
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: {3 * ms} }}
    events {{ metadata_id: 2 offset_ps: {5 * ms} duration_ps: {2 * ms} }}
    events {{ metadata_id: 3 offset_ps: {7 * ms} duration_ps: {1 * ms} }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "{_op('fusion.1')}"
    stats {{ metadata_id: 7 str_value: "{PAGED}" }} }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "{_op('copy.2', 'copy')}"
    stats {{ metadata_id: 7 ref_value: 9 }} }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "{_op('fusion.3')}" }} }}
  stat_metadata {{ key: 7 value {{ id: 7 name: "tf_op" }} }}
  stat_metadata {{ key: 9 value {{ id: 9 name: "{QUANT}" }} }} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 2 name: "python" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: {10 * ms} }}
    events {{ metadata_id: 2 offset_ps: {3 * ms} duration_ps: {2 * ms}
      stats {{ metadata_id: 3 int64_value: 7 }} }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "serve.admit" }} }}
  stat_metadata {{ key: 3 value {{ id: 3 name: "uid" }} }} }}
'''
    path = tmp_path / "cell" / "t.xplane.pb"
    path.parent.mkdir()
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    assert P._op_names(path.read_bytes()) == {_op("fusion.1"): PAGED,
                                              _op("copy.2", "copy"): QUANT}
    monkeypatch.setattr(P, "TRACE_ROOT", str(tmp_path))
    prog = P.load()
    assert prog["window"] == (0, 10 * MS)
    assert [n for _, _, n in prog["ops"]["/device:TPU:0"]] == \
        [PAGED, QUANT, ""]
    assert P.serve_spans(prog) == [("serve.admit", 3 * MS, 5 * MS,
                                    {"uid": 7})]
    assert P.scope_share(prog, "paged_kv") == pytest.approx(50)
    # idle [3,5) in serve.admit, [8,10) outside the program's spans
    assert P.idle_by_span(prog) == {"serve.admit": pytest.approx(2e-3),
                                    "outside serve spans":
                                        pytest.approx(2e-3)}
