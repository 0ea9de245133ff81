"""The controls of the check, at a size a test run holds (their chip
readings at full size are in PERF.md).

The program control lowers every ciphertext matmul to one bfloat16 pass.
On the CPU, which ignores matmul precision, the test plants the same
rounding where the server receives its operands: stored and query
ciphertexts rounded to bfloat16, as `Precision.DEFAULT` rounds a TPU
matmul's operands."""

import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import check, control, harness  # noqa: E402

TINY = {"config": {"n": 2048, "n_queries": 128, "ratio_k": 2},
        "traffic": {"max_rate_qps": 200, "clients": 8}}


def bf16(a):
    import jax.numpy as jnp
    return np.asarray(jnp.asarray(a, jnp.float32).astype(jnp.bfloat16)
                      .astype(jnp.float32))


@pytest.mark.parametrize("workload", ["sift128-flat-f32.single64",
                                      "sift128-flat-int8.single64"])
def test_the_program_at_one_bfloat16_pass_is_not_correct(workload,
                                                         monkeypatch):
    from repro.api import EncryptedQuery, SecureAnnService

    insert, submit = SecureAnnService.insert, SecureAnnService.submit

    def rounded_insert(self, tenant, name, C_sap, C_dce):
        return insert(self, tenant, name, bf16(C_sap), bf16(C_dce))

    def rounded_submit(self, req):
        q = EncryptedQuery(C_sap=bf16(req.query.C_sap), T=bf16(req.query.T))
        return submit(self, type(req)(req.tenant, req.collection, q,
                                      req.params, req.coalesce))

    monkeypatch.setattr(SecureAnnService, "insert", rounded_insert)
    monkeypatch.setattr(SecureAnnService, "submit", rounded_submit)
    res = harness.run_cell(workload, 2**31 + 7, 1.5, False, overrides=TINY)
    assert not res["correct"], res["checks"]
    # the refine's ordering is what one bfloat16 pass destroys
    c = res["checks"]["order_violations"]
    assert c["value"] > c["limit"]


def test_the_reference_control_is_read_on_the_run_queries():
    bench = harness.load_benchmark()
    cell = harness.find(bench["workloads"], "sift128-flat-f32.single64",
                        "workload")
    config = harness.merged(harness.load_config(bench, cell["config"]),
                            {"n": 65536, "n_queries": 400})
    mix = harness.load_traffic(cell["traffic"])
    r = control.reference_readings(config, mix, seed=2**31 + 5,
                                   seconds=1.0, answers=400)
    assert r["answers"] == 400
    assert set(r["numbers"]) == set(check.NUMBERS)
    # bfloat16 misorders near neighbours, but loses few of them
    assert r["numbers"]["order_violations"] > 0
    assert r["numbers"]["miss_rate"] < 0.1


def test_the_reference_itself_is_correct():
    bench = harness.load_benchmark()
    config = harness.merged(
        harness.load_config(bench, "sift128-flat-f32"),
        {"n": 65536, "n_queries": 400})
    from bench.data import make_corpus
    from bench.reference import exact_topk
    base, queries = (np.asarray(a) for a in make_corpus(config, 9))
    ref = exact_topk(base, queries[:200], 10)
    nums, _ = check.numbers(ref, np.arange(200), ref, base, queries,
                            lost=0, order_gap=float(config["order_gap"]))
    assert check.judge(nums, config["limits"])[0], nums
    assert nums["miss_rate"] == 0.0
