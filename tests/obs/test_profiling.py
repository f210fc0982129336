"""``profiling.span``: a ``jax.profiler.TraceAnnotation`` while a
capture started through the module runs, and otherwise the module-level
no-op (the ``faults.fire`` / ``flight.record`` / ``tracing.span``
idiom) that imports nothing."""

import subprocess
import sys
from pathlib import Path

from dstack_tpu.obs import profiling

REPO = Path(__file__).resolve().parents[2]


class TestNoCaptureIsNoop:
    def test_noop_rebinding_pinned(self):
        assert not profiling.is_tracing()
        assert profiling.span is profiling._noop_span
        with profiling.span("dtpu.engine.step", seq=1, phase="decode") as s:
            assert s is None
        # one shared context manager: no allocation per span
        assert profiling.span("a") is profiling.span("b", k=1)

    def test_no_capture_imports_no_jax(self):
        code = (
            "import sys\n"
            "from dstack_tpu.obs import profiling\n"
            "assert profiling.span is profiling._noop_span\n"
            "with profiling.span('dtpu.tick.host'):\n"
            "    pass\n"
            "with profiling.span('dtpu.engine.prefill', rows=2, cl=16):\n"
            "    pass\n"
            "assert not profiling.is_tracing()\n"
            "bad = [m for m in ('aiohttp', 'jax', 'numpy', 'jaxlib') "
            "if m in sys.modules]\n"
            "assert not bad, f'profiling pulled in {bad}'\n"
        )
        proc = subprocess.run(
            [sys.executable, "-c", code], cwd=REPO,
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stderr


class TestCaptureRebinds:
    def test_span_is_an_annotation_only_while_capturing(self, tmp_path):
        import jax

        assert profiling.span is profiling._noop_span
        profiling.start_trace(str(tmp_path))
        try:
            assert profiling.span is jax.profiler.TraceAnnotation
            with profiling.span("dtpu.tick.host"):
                pass
        finally:
            profiling.stop_trace()
        assert profiling.span is profiling._noop_span
