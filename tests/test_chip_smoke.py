"""chip_smoke.py's phases at CPU size: the HTTP serving phase and the
kernel-vs-gather read-path check on the ``--smoke`` config, and the
refusal of ``main()`` to run without a TPU."""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402
from repro.launch import serve  # noqa: E402


@pytest.fixture(scope="module")
def built():
    args = chip_smoke.smoke_args(smoke=True)
    return args, serve.build_engine(args)


def test_serve_and_read_path_phases(built):
    args, b = built
    reqs = chip_smoke.smoke_requests(args)
    res = chip_smoke.serve_phase(b.engine, b.cfg.vocab_size, reqs)
    assert sorted(res["tokens"]) == list(range(len(reqs)))
    assert set(res["finish"].values()) == {"length"}
    assert res["new_tokens"] == len(reqs) * args.new_tokens
    assert res["steady_quantum_ms"] is not None
    rp = chip_smoke.read_path_phase(
        b.engine, chip_smoke.probe_requests(b.engine, args))
    assert rp["rel_max_diff"] <= chip_smoke.READ_PATH_TOL
    assert rp["mosaic_in_step"] is False  # interpret mode on the CPU
    # the engine is empty again and serves a second time
    assert b.engine.n_active == 0 and not b.engine.has_work


def test_main_refuses_cpu():
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
