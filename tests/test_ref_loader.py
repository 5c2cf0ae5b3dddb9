"""tests/ref_loader.py where the reference snapshot is not mounted: its
callers skip by name, so tier-1's exit code says something."""

import pytest

import ref_loader


@pytest.fixture
def unmounted(monkeypatch, tmp_path):
    monkeypatch.setattr(ref_loader, "_ref_modules", {})
    monkeypatch.setattr(ref_loader, "REF_PATH", str(tmp_path / "absent"))
    return ref_loader.REF_PATH


def test_load_reference_skips_its_caller_by_name(unmounted):
    with pytest.raises(pytest.skip.Exception) as info:
        ref_loader.load_reference()
    assert str(info.value) == "`%s` is not mounted" % unmounted
    # a module that loads the reference at import skips whole, it is
    # not a collection error
    assert info.value.allow_module_level


def test_module_that_loads_at_import_is_skipped_not_an_error(unmounted):
    """Importing such a module raises the module-level skip, which
    pytest collects as one skipped module."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(__file__), "test_core_codecs.py")
    spec = importlib.util.spec_from_file_location("_probe_core_codecs", path)
    module = importlib.util.module_from_spec(spec)
    with pytest.raises(pytest.skip.Exception, match="is not mounted"):
        spec.loader.exec_module(module)
