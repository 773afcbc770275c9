"""What decides ``correct``: sound outputs pass, every control fails, and a
run whose timed path is broken underneath comes out as not correct."""

import json
import time
from pathlib import Path

import numpy as np
import pytest
from lib import check, reference

CDC = (4096, 16384, 65536)


def sound(tmp_path, n_chunks=3, n_bytes=1 << 19):
    """An Observed as a sound run would leave it: the reference stands in for
    the device path, files land as sent, counters say what the recipes hold."""
    rng = np.random.default_rng(5)
    base = rng.integers(0, 256, n_bytes, dtype=np.uint8)
    sent, rows, device_rows, digests, frames = [], {}, {}, {}, []
    for i in range(n_chunks):
        row = base.copy()
        if i:
            row[8192 * i : 8192 * i + 4096] = rng.integers(0, 256, 4096, dtype=np.uint8)
        path = tmp_path / f"chunk_{i}.bin"
        path.write_bytes(row.tobytes())
        s = check.Sent(index=i, chunk_id=f"c{i}", key=check.row_key(row), digest=check.bytes_digest(row), n_bytes=n_bytes,
                       src_path=path, dst_path=path, posted_at=time.time(), completed_at=time.time())
        sent.append(s)
        rows[i] = row
        device_rows[i] = reference.cdc_and_fingerprints(row, *CDC)
        digests[i] = check.file_digest(path)
        frames.append({"chunk_id": s.chunk_id, "codec": 3, "raw_bytes": n_bytes, "wire_bytes": 1000})
    segments, fewest, _ = check.expected_refs(device_rows, list(range(n_chunks)))
    obs = check.Observed(
        sent=sent, file_digests=digests, device_rows=device_rows, row_bytes=rows.__getitem__,
        counters={"batch_rows": n_chunks, "stage_failures": 0, "segments": segments, "ref_segments": fewest},
        frames=frames, gateway_errors=0, as_built_departures=[], cdc=CDC, wire_codec_id=3,
    )
    check.compute_reference(obs)
    return obs


def over(compared):
    return {k for k, v in compared.items() if v["value"] > v["limit"]}


def test_sound_outputs_pass_every_comparison(tmp_path):
    compared = check.compare(sound(tmp_path))
    assert over(compared) == set() and check.passed(compared)
    assert all(v["limit"] == 0 for v in compared.values())


@pytest.mark.parametrize(
    "control, has_to_fail",
    [("fp_4_lanes", "rows_fingerprints_differ"), ("cdc_avg_halved", "rows_ends_differ"), ("restore_flips_byte", "files_not_identical")],
)
def test_every_control_comes_out_as_not_correct(tmp_path, control, has_to_fail):
    obs = sound(tmp_path)
    check.CONTROLS[control](obs)
    compared = check.compare(obs)
    assert not check.passed(compared) and has_to_fail in over(compared)


@pytest.mark.parametrize(
    "break_it, has_to_fail",
    [
        (lambda o: o.sent[2].__setattr__("completed_at", None), "chunks_never_landed"),
        (lambda o: o.device_rows.pop(1), "rows_off_device"),
        (lambda o: o.counters.__setitem__("batch_rows", 2), "rows_off_device"),
        (lambda o: o.counters.__setitem__("ref_segments", o.counters["ref_segments"] - 1), "ref_segments_off"),
        (lambda o: o.counters.__setitem__("segments", o.counters["segments"] + 1), "segments_off"),
        (lambda o: o.frames.pop(), "frames_missing"),
        (lambda o: o.frames[0].__setitem__("codec", 0), "frames_other_codec"),
        (lambda o: o.frames[0].__setitem__("raw_bytes", 5), "frames_wrong_length"),
        (lambda o: o.__setattr__("gateway_errors", 1), "gateway_errors"),
        (lambda o: o.as_built_departures.append("gw_src.tls: built False, stated True"), "as_built_departures"),
        (lambda o: o.file_digests.__setitem__(0, None), "files_not_identical"),
    ],
)
def test_each_number_catches_its_fault(tmp_path, break_it, has_to_fail):
    obs = sound(tmp_path)
    break_it(obs)
    assert has_to_fail in over(check.compare(obs))


@pytest.mark.parametrize("index", [0, 1, 2])
def test_a_wrong_fingerprint_on_any_row_the_set_up_row_too_is_caught(tmp_path, index):
    obs = sound(tmp_path)
    ends, fps = obs.device_rows[index]
    obs.device_rows[index] = (ends, [bytes([fps[0][0] ^ 1]) + fps[0][1:]] + fps[1:])
    assert "rows_fingerprints_differ" in over(check.compare(obs))
    obs = sound(tmp_path)
    ends, fps = obs.device_rows[index]
    obs.device_rows[index] = (np.concatenate([[ends[0] - 1], ends[1:]]), fps)
    assert "rows_ends_differ" in over(check.compare(obs))


def test_refs_shared_between_window_chunks_widen_the_expected_count_not_the_fault(tmp_path):
    obs = sound(tmp_path)
    obs.device_rows[2] = obs.device_rows[1]  # chunk 2 repeats chunk 1: its new segments may or may not be REFs
    segments, fewest, most = check.expected_refs(obs.device_rows, [0, 1, 2])
    assert most > fewest
    for refs in (fewest, most):
        obs.counters.update(segments=segments, ref_segments=refs)
        assert "ref_segments_off" not in over(check.compare(obs))


# ---- several set-up rows: what the source's index holds when each row is posted

HAND_ROWS = {i: (None, [bytes([c]) for c in fps]) for i, fps in enumerate([b"ab", b"cda", b"ef", b"aceg", b"fgh"])}


@pytest.mark.parametrize(
    "setup_rows, fewest, most",
    [
        ((0, 1, 2), 5, 6),  # row 1 finds a in row 0; row 3 finds a, c, e in the set-up; row 4 f (g only in row 3)
        ((0,), 2, 6),  # one set-up row: a in rows 1 and 3; the rest only among rows sent before
    ],
    ids=["three_setup_rows", "one_setup_row"],
)
def test_expected_refs_by_hand_for_set_up_rows(setup_rows, fewest, most):
    assert check.expected_refs(HAND_ROWS, [0, 1, 2, 3, 4], setup_rows) == (14, fewest, most)


def test_one_set_up_row_reads_what_the_rule_before_several_read():
    """The rule before set-up rows of their own: the first row of ``order``
    is the set-up, every later row finds it."""

    def before(rows, order):
        setup, segments, fewest, most, seen = set(rows[order[0]][1]), 0, 0, 0, set()
        for n, idx in enumerate(order):
            own = set()
            for fp in rows[idx][1]:
                segments += 1
                if (n > 0 and fp in setup) or fp in own:
                    fewest, most = fewest + 1, most + 1
                elif fp in seen:
                    most += 1
                own.add(fp)
            seen |= own
        return segments, fewest, most

    assert check.expected_refs(HAND_ROWS, [0, 1, 2, 3, 4]) == before(HAND_ROWS, [0, 1, 2, 3, 4])
    # real rows, each content twice: rows 0 and 2 alike, rows 1 and 3 alike
    rows = {i: reference.cdc_and_fingerprints(np.random.default_rng([3, i % 2]).integers(0, 256, 1 << 18, dtype=np.uint8), *CDC) for i in range(4)}
    assert check.expected_refs(rows, [0, 1, 2, 3]) == before(rows, [0, 1, 2, 3])


def regions(tmp_path, n_bytes=1 << 19):
    """A sound run of three set-up rows, each a region of its own, and two
    window rows that are set-up rows 1 and 2 with 4 KiB rewritten: the window
    REFs rows 1 and 2 and never row 0."""
    rng = np.random.default_rng(11)
    bases = [rng.integers(0, 256, n_bytes, dtype=np.uint8) for _ in range(3)]
    rows = {i: bases[i] for i in range(3)}
    for i, r in ((3, 1), (4, 2)):
        rows[i] = bases[r].copy()
        rows[i][65536 : 65536 + 4096] = rng.integers(0, 256, 4096, dtype=np.uint8)
    sent, digests, frames = [], {}, []
    for i, row in rows.items():
        path = tmp_path / f"chunk_{i}.bin"
        path.write_bytes(row.tobytes())
        s = check.Sent(index=i, chunk_id=f"c{i}", key=check.row_key(row), digest=check.bytes_digest(row), n_bytes=n_bytes,
                       src_path=path, dst_path=path, posted_at=time.time(), completed_at=time.time())
        sent.append(s)
        digests[i] = check.file_digest(path)
        frames.append({"chunk_id": s.chunk_id, "codec": 3, "raw_bytes": n_bytes, "wire_bytes": 1000})
    device_rows = {i: reference.cdc_and_fingerprints(row, *CDC) for i, row in rows.items()}
    segments, fewest, _ = check.expected_refs(device_rows, sorted(rows), (0, 1, 2))
    obs = check.Observed(
        sent=sent, file_digests=digests, device_rows=device_rows, row_bytes=rows.__getitem__,
        counters={"batch_rows": len(rows), "stage_failures": 0, "segments": segments, "ref_segments": fewest},
        frames=frames, gateway_errors=0, as_built_departures=[], cdc=CDC, wire_codec_id=3, setup_rows=(0, 1, 2),
    )
    check.compute_reference(obs)
    return obs


def test_a_sender_that_forgot_a_set_up_row_fails_the_new_fewest_and_passed_the_old(tmp_path):
    obs = regions(tmp_path)
    assert over(check.compare(obs)) == set()
    row2 = set(obs.device_rows[2][1])
    forgotten = sum(1 for i in (3, 4) for fp in obs.device_rows[i][1] if fp in row2)
    assert forgotten > len(obs.device_rows[4][1]) // 2  # row 4 is row 2 but for 4 KiB: most of its segments
    obs.counters["ref_segments"] -= forgotten  # those went as literals: the index had lost row 2
    compared = check.compare(obs)
    assert "ref_segments_off" in over(compared) and compared["ref_segments_off"]["value"] == forgotten
    obs.setup_rows = (0,)  # the rule before: row 0 alone is the set-up
    assert "ref_segments_off" not in over(check.compare(obs))


# ---- a whole run on the CPU backend, at chunks 64 times smaller, with the
# timed path broken underneath: the harness's look for a chip is skipped
# (a rehearsal), everything else is the code a chip run drives


def rehearse(capsys, cell, trace="0"):
    import run

    rc = run.main(["--workload", cell, "--seed", "4000000001", "--seconds", "3", "--trace", trace, "--rehearse-scale", "64"])
    captured = capsys.readouterr()
    lines = [line for line in captured.out.strip().splitlines() if line.startswith("{")]
    return rc, (json.loads(lines[-1]) if lines else None), captured.err


def first_cell():
    spec = json.loads((Path(__file__).resolve().parents[2] / "BENCHMARK.json").read_text())
    return spec["workloads"][0]["name"]


def test_a_sound_rehearsal_passes_its_checks_and_is_still_never_correct(capsys):
    rc, result, err = rehearse(capsys, first_cell())
    assert rc == 1 and result["correct"] is False and result["metrics"] == {}
    assert result["rehearsal"]["checks_passed"] is True, err[-3000:]
    assert list(result)[-1] == "compared" and "correct: False" in err.splitlines()[-1]


def test_a_fingerprint_altered_where_it_is_produced_is_not_correct(capsys, monkeypatch):
    from skyplane_tpu.ops import batch_runner

    real = batch_runner.finalize_row

    def altered(lanes_row, ends):
        fps = real(lanes_row, ends)
        return [bytes([fps[0][0] ^ 1]) + fps[0][1:]] + fps[1:]

    monkeypatch.setattr(batch_runner, "finalize_row", altered)
    rc, result, err = rehearse(capsys, first_cell())
    assert rc != 0 and (result is None or result["rehearsal"]["checks_passed"] is False)
    assert "correct: False" in err


def test_a_byte_altered_where_the_sink_lands_it_is_not_correct(capsys, monkeypatch):
    from skyplane_tpu.gateway.operators.gateway_receiver import GatewayReceiver

    real = GatewayReceiver._land

    def altered(fpath, data):
        data = bytearray(data)
        data[len(data) // 2] ^= 1
        real(fpath, bytes(data))

    monkeypatch.setattr(GatewayReceiver, "_land", staticmethod(altered))
    rc, result, err = rehearse(capsys, first_cell())
    assert result["rehearsal"]["checks_passed"] is False
    assert result["compared"]["files_not_identical"]["value"] > 0 and result["failed"] > 0


def test_rows_that_skip_the_device_path_are_not_correct(capsys, monkeypatch):
    from skyplane_tpu.ops.pipeline import DataPathProcessor

    monkeypatch.setattr(DataPathProcessor, "_on_accelerator", staticmethod(lambda: False))
    rc, result, err = rehearse(capsys, first_cell())
    assert result["rehearsal"]["checks_passed"] is False and result["compared"]["rows_off_device"]["value"] > 0


def test_off_the_chip_a_run_prints_no_result(capsys):
    import run

    rc = run.main(["--workload", first_cell(), "--seed", "1", "--seconds", "3", "--trace", "0"])
    captured = capsys.readouterr()
    assert rc == 2 and captured.out == "" and "no result" in captured.err
