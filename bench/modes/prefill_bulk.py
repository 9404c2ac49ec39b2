"""Offline classification of token rows through an LM front end:
``serve_batches`` called in a closed loop.

Each call serves ``requests_per_call`` requests of ``request_rows`` rows
of ``row_tokens`` token ids, drawn in a seeded order from a pool of
``pool_rows`` rows (ids Zipf(``zipf_s``) over a seeded permutation of
the vocabulary), ``max_batch`` rows a batch, ``depth`` batches in
flight, with no padding rows. The batch shape is warmed during set-up;
the window runs whole calls until ``--seconds`` have passed.
``rows_per_s`` is the rows answered over the window's length.

The artifact answers each row with one int32 row (the program's
``FrontendMemhd.layout``): class, packed query, held-expert slot counts,
dropped slots and the row's token count. After the window the plain
reference computes every pool row once, and the checks compare what the
timed path returned:

* ``missing_answers``: requests with no answer;
* ``dropped_slots``: held-expert slots the timed path left uncomputed;
* ``query_bits_differ_ppm``: query bits that differ from the
  reference's sign bits, per million bits of the distinct rows answered
  (a bit counts once if any answer of its row differs);
* ``slot_counts_differ_ppm``: the routing: |timed path's held-expert
  slot counts - the reference's|, summed over the distinct rows answered,
  their MoE layers and held experts, per million reference slots (the
  largest difference over a row's answers);
* ``mismatch_ppm``: distinct pool rows answered with another class than
  the reference's at least once, per million distinct rows answered;
* ``search_mismatch``: answered rows whose class is not the one the
  reference's search gives for the query bits the timed path returned
  with it: the search checked exactly, whatever the prefill's rounding
  did to the bits.
"""
import gc
import time

import numpy as np

from bench import gen
from bench.harness import Window, timed
from bench.modes import Result

ORDERS = 16  # distinct request orders, cycled over the calls


def columns(cfg: dict) -> dict:
    """Column slices of an answer row, from the configuration's sizes."""
    words = -(-cfg["dim"] // 32)
    n = (cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]) * cfg[
        "experts_held"]
    return {"cls": 0, "query": slice(1, 1 + words),
            "counts": slice(1 + words, 1 + words + n),
            "dropped": 1 + words + n, "tokens": 2 + words + n}


def query_bits(words: np.ndarray, dim: int) -> np.ndarray:
    """(n, D/32) int32 packed queries -> (n, D) bool, LSB-first."""
    u8 = np.ascontiguousarray(words.astype("<i4")).view(np.uint8)
    return np.unpackbits(u8, axis=-1, bitorder="little")[:, :dim].astype(
        bool)


def run(run, system) -> Result:
    from repro.launch.serve_memhd import Request, serve_batches

    t = run.traffic
    per_req = t["request_rows"]
    with timed(run.phases, "pool"):
        pool = system.rows(t["pool_rows"], tokens=t["row_tokens"],
                           zipf_s=t["zipf_s"])
    n_req = t["pool_rows"] // per_req
    requests = [Request(rid=i, feats=pool[i * per_req:(i + 1) * per_req])
                for i in range(n_req)]
    orders = [gen.bulk_order(run.seed, k, n_req)[:t["requests_per_call"]]
              for k in range(ORDERS)]
    if (t["requests_per_call"] * per_req) % t["max_batch"]:
        raise ValueError("bulk calls must fill every batch")
    opts = dict(max_batch=t["max_batch"], tile=t["max_batch"], fused=True,
                depth=t["depth"])
    with timed(run.phases, "warmup"):
        serve_batches(system.artifact, [requests[i] for i in orders[0]],
                      warmup=True, **opts)

    served = []  # (order index, responses)
    with Window(run) as w:
        deadline = w.t0 + run.seconds
        call = 0
        while time.perf_counter() < deadline:
            k = call % ORDERS
            with w.call("serve_batches"):
                responses, stats = serve_batches(
                    system.artifact, [requests[i] for i in orders[k]],
                    warmup=False, **opts)
            served.append((k, responses))
            w.add(rows=stats["rows_real"], calls=1)
            call += 1

    system.artifact = system.model = None
    gc.collect()
    t_check = time.perf_counter()
    want_cls, want_bits, want_counts = system.answers(pool)
    cols = columns(system.cfg)
    dim = system.cfg["dim"]
    want_cls = want_cls.reshape(n_req, per_req)
    want_bits = want_bits.reshape(n_req, per_req, dim)
    want_counts = want_counts.reshape(n_req, per_req, -1)
    attempted = failed = dropped = 0
    seen = np.zeros((n_req, per_req), bool)
    wrong = np.zeros((n_req, per_req), bool)
    flipped = np.zeros((n_req, per_req, dim), bool)
    moved = np.zeros(want_counts.shape, np.int64)
    got_bits, got_cls = [], []
    for k, responses in served:
        for i in orders[k]:
            attempted += 1
            got = responses.get(int(i))
            if got is None:
                failed += 1
                continue
            got = np.asarray(got)
            seen[i] = True
            bits = query_bits(got[:, cols["query"]], dim)
            got_bits.append(bits)
            got_cls.append(got[:, cols["cls"]])
            wrong[i] |= got[:, cols["cls"]] != want_cls[i]
            flipped[i] |= bits != want_bits[i]
            moved[i] = np.maximum(moved[i], np.abs(
                got[:, cols["counts"]] - want_counts[i]))
            dropped += int(got[:, cols["dropped"]].sum())
    searched = 0
    if got_bits:
        searched = int(np.sum(system.search(np.concatenate(got_bits))
                              != np.concatenate(got_cls)))
    n_seen = max(int(seen.sum()), 1)
    slots = max(int(want_counts[seen].sum()), 1)
    return Result(
        end_to_end={"rows_per_s": run.counts["rows"] / run.window_s},
        attempted=attempted, failed=failed,
        checks={"missing_answers": failed, "dropped_slots": dropped,
                "query_bits_differ_ppm": float(
                    1e6 * flipped.sum() / (n_seen * dim)),
                "slot_counts_differ_ppm": float(
                    1e6 * moved[seen].sum() / slots),
                "mismatch_ppm": float(1e6 * wrong.sum() / n_seen),
                "search_mismatch": searched},
        notes={"reference_s": time.perf_counter() - t_check})
