"""The knee sweep's reading at toy sizes on the CPU: a light rate is
sustained, with every end-to-end metric beside it."""
import json

import _tiny
import sweep


def test_light_rate_is_sustained(tmp_path):
    wl = _tiny.workload("open")
    # a 6 s window puts 9 turns in each half, so the halves' median TTFTs
    # (tens of milliseconds on the CPU) are not one or two turns' jitter
    r = sweep.reading(wl, 3.0, 2 ** 31 + 21, 6.0, tmp_path,
                      require_tpu=False)
    assert r["rate"] == 3.0 and r["measured"] > 0
    assert r["unfinished"] == 0 and r["sustained"], json.dumps(r)
    assert r["output_tok_s"] > 0 and r["ttft_p95_s"] > 0
