"""Native artefacts are keyed by the content of their sources
(native/build.py): an mtime says nothing after a checkout or a copy."""

import os
import shutil
import tempfile

import pytest

from adlb_tpu.native import build

pytestmark = pytest.mark.skipif(
    shutil.which("gcc") is None, reason="no C toolchain")


def _build(src: str, hdr: str) -> str:
    return build.build_artifact(
        "prog", ["gcc", "-o", "{out}", src], [src, hdr])


def test_touch_does_not_rebuild_but_a_changed_byte_does(
        tmp_path, monkeypatch):
    monkeypatch.setattr(build, "BUILD_DIR", str(tmp_path / "_build"))
    src, hdr = str(tmp_path / "prog.c"), str(tmp_path / "prog.h")
    with open(hdr, "w") as f:
        f.write("#define RC 0\n")
    with open(src, "w") as f:
        f.write('#include "prog.h"\nint main(void) { return RC; }\n')

    first = _build(src, hdr)
    assert first.startswith(build.BUILD_DIR) and os.path.exists(first)
    built_at = os.stat(first).st_mtime_ns

    # newer mtimes, same bytes: same artefact, not recompiled
    for path in (src, hdr):
        os.utime(path, ns=(built_at + 10**10, built_at + 10**10))
    assert _build(src, hdr) == first
    assert os.stat(first).st_mtime_ns == built_at

    # one byte of a HEADER changes: another key, another artefact
    with open(hdr, "w") as f:
        f.write("#define RC 1\n")
    second = _build(src, hdr)
    assert second != first and os.path.exists(second)
    assert os.path.exists(first)  # nobody's binary is pulled from under it


def test_failed_compile_is_remembered_per_content(tmp_path, monkeypatch):
    monkeypatch.setattr(build, "BUILD_DIR", str(tmp_path / "_build"))
    src, hdr = str(tmp_path / "bad.c"), str(tmp_path / "bad.h")
    open(hdr, "w").close()
    with open(src, "w") as f:
        f.write("int main(void) { return nonsense; }\n")
    with pytest.raises(build.BuildError, match="nonsense"):
        _build(src, hdr)
    with pytest.raises(build.BuildError, match="failed previously"):
        _build(src, hdr)
    with open(src, "w") as f:  # fixed source: new key, marker moot
        f.write("int main(void) { return 0; }\n")
    assert os.path.exists(_build(src, hdr))


def test_repo_artefacts_live_inside_the_checkout():
    from adlb_tpu.native.capi import build_example, build_libadlb

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    inside = os.path.join(repo, "adlb_tpu", "native", "_build") + os.sep
    exe = build_example(os.path.join(repo, "examples", "capi_smoke.c"))
    for path in (build_libadlb(), build.ensure_serverd(), exe):
        assert path.startswith(inside), path
        assert not path.startswith(tempfile.gettempdir() + os.sep)
