"""The reduction from a profiler trace to device busy time, idle share,
kernel time and idle gaps, on synthetic traces and on one recorded on
the CPU."""
import pytest

from chipbench import trace as tr


def ev(name, start, dur, dev=0, **tags):
    return tr.Event(name, float(start), float(dur), tuple(tags.items()), dev)


def test_union_merges_overlaps_and_keeps_gaps():
    evs = [ev("a", 0, 10), ev("b", 5, 10), ev("c", 30, 5), ev("d", 31, 1)]
    assert tr.union_ns(evs) == 15 + 5


def test_busy_seconds_averages_over_devices():
    t = tr.Trace([ev("a", 0, 2e9, 0), ev("b", 0, 1e9, 1),
                  ev("c", 5e8, 1e9, 1)], [], 2)
    assert t.n_devices == 2
    assert tr.busy_seconds(t) == pytest.approx((2.0 + 1.5) / 2)


def test_kernel_time_is_the_custom_call_inside_its_program():
    mods = [ev("jit_kv_restore_pallas(123)", 0, 400),
            ev("jit_paged_attention_pallas(9)", 400, 800),
            ev("jit_kv_restore_pallas(123)", 2000, 100)]
    ops = [ev("%kv_restore_pallas.1 = bf16[8,8,128] custom-call(...)", 10,
              300),
           ev("%copy.2 = s32[57] copy(s32[57] %slots)", 320, 50),
           ev("%custom-call.7 = bf16[4,56,128] custom-call(...)", 450, 700),
           ev("%fusion.1 = bf16[4,56,128] fusion(...)", 1160, 30),
           ev("%custom-call.3 = bf16[8,8,128] custom-call(...)", 2010, 80),
           # a custom call of another program
           ev("%custom-call.9 = f32[4] custom-call(...)", 3000, 70)]
    t = tr.Trace(ops, [], 1, mods)
    assert tr.kernel_seconds(t, "kv_restore_pallas") == \
        pytest.approx(380e-9)
    assert tr.kernel_seconds(t, "paged_attention_pallas") == \
        pytest.approx(700e-9)
    assert tr.kernel_seconds(t, "no_such_kernel") == 0.0
    assert tr.kernel_seconds(tr.Trace(ops, [], 1), "kv_restore_pallas") \
        == 0.0


def test_idle_gaps_are_named_by_the_innermost_host_span():
    ops = [ev("x", 10, 10), ev("y", 50, 10), ev("z", 90, 5)]
    host = [ev("chipbench.window", 0, 100), ev("chipbench.step", 20, 35),
            ev("decode", 22, 8)]
    t = tr.Trace(ops, host, 1)
    gaps = tr.idle_gaps(t, (0.0, 100.0), n=3)
    # gaps: 20-50 (30 ns, middle 35 -> step), 60-90 (30, window),
    # 0-10 (10, window)
    assert [g[1] for g in gaps] == pytest.approx([30e-9, 30e-9, 10e-9])
    assert gaps[0][0] == "chipbench.step"
    assert gaps[1][0] == "chipbench.window"
    assert tr.host_label(host, 26) == "decode"
    assert tr.host_label(host, 500) == "<no host span>"


def test_top_ops_groups_by_program_and_instruction():
    mods = [ev("jit_scatter(1)", 0, 100), ev("jit_dot(2)", 100, 100)]
    t = tr.Trace([ev("%copy.1 = bf16[4] copy(x)", 0, 5),
                  ev("%copy.1 = bf16[4] copy(x)", 10, 5),
                  ev("%dot.2 = f32[2] dot(a, b)", 120, 7),
                  ev("%lost = f32[2] add(a, b)", 500, 1)], [], 1, mods)
    top = tr.top_ops(t, n=3)
    assert top[0][0] == "jit_scatter:%copy.1"
    assert top[0][1] == pytest.approx(10e-9)
    assert top[1][0] == "jit_dot:%dot.2"
    assert top[2][0] == ":%lost"


def test_load_reads_host_spans_of_a_trace_recorded_here(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("chipbench.window"):
        jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(tr.find_xplane(str(tmp_path)))
    assert "chipbench.window" in [e.name for e in t.host]
    # the CPU has no TPU plane: no device operations, no busy time
    assert t.ops == [] and t.n_devices == 0
    assert tr.busy_seconds(t) == 0.0
    assert any("plane" in line for line in
               tr.describe(tr.find_xplane(str(tmp_path))))


def test_load_keeps_each_host_spans_arguments_as_strings(tmp_path):
    """A new reader can take a new span's shapes from its own
    arguments."""
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("chipbench.window"):
        with jax.profiler.TraceAnnotation("kvf.test", rid=7, kind="k",
                                          tokens=2112):
            jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(tr.find_xplane(str(tmp_path)))
    span, = [e for e in t.host if e.name == "kvf.test"]
    assert (span.tag("rid"), span.tag("kind"), span.tag("tokens")) == \
        ("7", "k", "2112")
    assert span.tag("no_such_argument") == ""
    win, = [e for e in t.host if e.name == "chipbench.window"]
    assert win.start_ns <= span.start_ns and span.end_ns <= win.end_ns
