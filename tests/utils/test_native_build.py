"""The native core's freshness is a hash of native/*, stored beside the
library: a build/ copied from elsewhere rebuilds from the committed
sources."""
import os

from parsec_tpu import _native


def test_library_stamp_matches_committed_sources():
    stamp = _native._LIB_PATH + ".srchash"
    if os.environ.get("PTC_NATIVE_LIB"):
        return  # an instrumented override owns its own freshness
    with open(stamp) as f:
        assert f.read().strip() == _native.SOURCE_HASH
    assert _native.SOURCE_HASH == _native.source_hash()
    assert len(_native.SOURCE_HASH) == 64
