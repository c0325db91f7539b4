import glob

from gordo_tpu.utils.profiling import annotate, maybe_trace


def test_maybe_trace_noop_without_env(monkeypatch):
    monkeypatch.delenv("GORDO_TPU_PROFILE_DIR", raising=False)
    with maybe_trace("x"):
        pass
    with annotate("y"):  # no session: the annotation records nothing
        pass


def test_maybe_trace_writes_trace(monkeypatch, tmp_path):
    monkeypatch.setenv("GORDO_TPU_PROFILE_DIR", str(tmp_path))
    import jax.numpy as jnp

    with maybe_trace("unit"):
        with annotate("region"):
            (jnp.ones((4, 4)) @ jnp.ones((4, 4))).block_until_ready()
    # the profiler writes its plugin dir layout under <dir>/unit
    assert (tmp_path / "unit").exists()
    assert any((tmp_path / "unit").rglob("*")), "no trace output written"
    assert "region" in host_events(tmp_path / "unit")


def host_events(directory):
    from jax.profiler import ProfileData

    (path,) = glob.glob(str(directory / "plugins" / "profile" / "*" / "*.xplane.pb"))
    return {
        event.name
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for event in line.events
    }


def test_annotate_is_a_trace_annotation_whoever_owns_the_session(monkeypatch, tmp_path):
    """Not gated on ``GORDO_TPU_PROFILE_DIR``: inside a session somebody
    else started, the region lies in the trace's host plane."""
    import jax

    monkeypatch.delenv("GORDO_TPU_PROFILE_DIR", raising=False)
    assert isinstance(annotate("free"), jax.profiler.TraceAnnotation)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with annotate("somebody-elses-session"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert "somebody-elses-session" in host_events(tmp_path)
