"""Live ingest frontend (ISSUE 19): wire codec, ring-buffer assembler,
socket sources, ledger accounting — and the byte identity of a lossless
local feed with the disk search.

Everything here runs on localhost sockets and tiny arrays; no test
needs more than a few hundred ms of JAX work.
"""

import threading
import time

import numpy as np
import pytest

from pulsarutils_tpu.faults import reasons
from pulsarutils_tpu.ingest import (ChunkAssembler, TCPSource, UDPSource,
                                    feed_tcp, feed_udp)
from pulsarutils_tpu.io.packets import (HEADER_SIZE, PacketCorruptError,
                                        PacketError, decode_packet,
                                        encode_packet, packetize_array,
                                        read_packet_stream)
from pulsarutils_tpu.obs.health import CRITICAL, DEGRADED, OK, HealthEngine


def make_block(nchan, nsamps, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(10.0, 1.0, (nchan, nsamps)).astype(np.float32)


def packets_of(block, spp, **kw):
    """Decoded Packet list for a float block (push-side test helper)."""
    return [decode_packet(buf)[0]
            for buf in packetize_array(block, samples_per_packet=spp,
                                       **kw)]


def drain(asm):
    """Collect every queued chunk from a closed assembler."""
    return {istart: np.asarray(chunk) for istart, chunk in asm.chunks()}


def reader_stream(parts):
    """A read(n) callable over a list of byte strings (socket stub)."""
    buf = bytearray(b"".join(parts))

    def read(n):
        out = bytes(buf[:n])
        del buf[:n]
        return out

    return read


# -- wire codec ---------------------------------------------------------------

def test_packet_roundtrip_float():
    frames = np.arange(12, dtype=np.float32).reshape(3, 4)
    buf = encode_packet(seq=7, sample0=1024, nchan=4, nbits=0,
                        payload=frames.tobytes())
    pkt, consumed = decode_packet(buf + b"trailing")
    assert consumed == len(buf)
    assert (pkt.seq, pkt.sample0, pkt.nsamps, pkt.nchan) == (7, 1024, 3, 4)
    assert pkt.nbits == 0 and not pkt.band_descending
    np.testing.assert_array_equal(pkt.frames(), frames)


def test_packet_roundtrip_packed_descending():
    rows = np.arange(8, dtype=np.uint8).reshape(2, 4)  # 2 frames, 4 B
    buf = encode_packet(seq=0, sample0=0, nchan=16, nbits=2,
                        payload=rows.tobytes(), band_descending=True)
    pkt, _ = decode_packet(buf)
    assert pkt.nbits == 2 and pkt.band_descending
    np.testing.assert_array_equal(pkt.frames(), rows)


def test_packet_header_rejections():
    good = encode_packet(seq=0, sample0=0, nchan=2, nbits=0,
                         payload=np.zeros(4, np.float32).tobytes())
    with pytest.raises(PacketError, match="magic"):
        decode_packet(b"XXXX" + good[4:])
    with pytest.raises(PacketError, match="version"):
        decode_packet(good[:4] + b"\x09" + good[5:])
    with pytest.raises(PacketError, match="short header"):
        decode_packet(good[:HEADER_SIZE - 1])
    with pytest.raises(PacketError, match="short payload"):
        decode_packet(good[:-1])
    with pytest.raises(PacketError, match="whole number"):
        encode_packet(seq=0, sample0=0, nchan=2, nbits=0, payload=b"abc")


def test_packet_crc_reject_is_distinct():
    buf = bytearray(encode_packet(
        seq=3, sample0=0, nchan=2, nbits=0,
        payload=np.ones(4, np.float32).tobytes()))
    buf[HEADER_SIZE] ^= 0xFF
    with pytest.raises(PacketCorruptError, match="seq 3"):
        decode_packet(bytes(buf))


def test_read_packet_stream_skips_corrupt_keeps_framing():
    block = make_block(2, 6)
    bufs = packetize_array(block, samples_per_packet=2)
    assert len(bufs) == 3
    torn = bytearray(bufs[1])
    torn[HEADER_SIZE] ^= 0xFF  # CRC reject, framing intact
    skipped = []
    got = list(read_packet_stream(
        reader_stream([bufs[0], bytes(torn), bufs[2]]),
        on_corrupt=skipped.append))
    assert [p.seq for p in got] == [0, 2]
    assert len(skipped) == 1
    # without the handler the corruption propagates
    with pytest.raises(PacketCorruptError):
        list(read_packet_stream(
            reader_stream([bufs[0], bytes(torn), bufs[2]])))


def test_read_packet_stream_clean_eof_vs_torn():
    block = make_block(2, 4)
    bufs = packetize_array(block, samples_per_packet=2)
    assert [p.seq for p in
            read_packet_stream(reader_stream(bufs))] == [0, 1]
    with pytest.raises(PacketError, match="mid-packet"):
        list(read_packet_stream(reader_stream([bufs[0][:-3]])))


def test_packetize_array_reassembles():
    block = make_block(4, 10, seed=2)
    pkts = packets_of(block, 4)
    assert [p.nsamps for p in pkts] == [4, 4, 2]
    assert [p.sample0 for p in pkts] == [0, 4, 8]
    rebuilt = np.concatenate([p.frames() for p in pkts]).T
    np.testing.assert_array_equal(rebuilt, block)


# -- assembler ----------------------------------------------------------------

def test_assembler_in_order_byte_identity():
    nchan, step = 8, 64
    block = make_block(nchan, 3 * step, seed=1)
    asm = ChunkAssembler(nchan=nchan, step=step)
    for pkt in packets_of(block, 16):
        asm.push(pkt)
    asm.close()
    got = drain(asm)
    assert sorted(got) == [0, step, 2 * step]
    for s, chunk in got.items():
        assert chunk.tobytes() == \
            np.ascontiguousarray(block[:, s:s + step]).tobytes()
    led = asm.ledger
    assert led.observed == led.arrived == led.delivered == 3 * step
    assert led.gap_filled == 0 and led.unaccounted() == 0
    assert not led.journal


def test_assembler_reorder_within_window():
    nchan, step = 4, 64
    block = make_block(nchan, 2 * step, seed=3)
    pkts = packets_of(block, 16)
    pkts[2], pkts[3] = pkts[3], pkts[2]  # swap two mid-stream packets
    asm = ChunkAssembler(nchan=nchan, step=step, reorder_window=32)
    for pkt in pkts:
        asm.push(pkt)
    asm.close()
    got = drain(asm)
    assert asm.reordered >= 1
    for s in (0, step):
        assert got[s].tobytes() == \
            np.ascontiguousarray(block[:, s:s + step]).tobytes()
    assert asm.ledger.unaccounted() == 0


def test_assembler_gap_zero_filled_and_accounted():
    nchan, step, spp = 4, 64, 16
    block = make_block(nchan, 2 * step, seed=4)
    pkts = packets_of(block, spp)
    lost = pkts.pop(1)  # samples 16..32 never arrive
    asm = ChunkAssembler(nchan=nchan, step=step)
    for pkt in pkts:
        asm.push(pkt)
    asm.close()
    got = drain(asm)
    expected = block.copy()
    expected[:, lost.sample0:lost.sample0 + spp] = 0.0
    assert got[0].tobytes() == \
        np.ascontiguousarray(expected[:, :step]).tobytes()
    led = asm.ledger
    assert led.gap_filled == spp
    assert led.arrived + led.gap_filled == led.observed
    assert led.unaccounted() == 0
    assert not led.journal  # 25% loss is sanitized, not quarantined


def test_assembler_unrecoverable_gap_quarantines_feed_gap(tmp_path):
    from pulsarutils_tpu.faults.policy import QuarantineManifest

    nchan, step, spp = 4, 64, 8
    block = make_block(nchan, 2 * step, seed=5)
    pkts = packets_of(block, spp)
    # keep only the first packet of chunk 0: 87.5% loss > max_zero_frac
    manifest = QuarantineManifest(str(tmp_path), "ingest")
    asm = ChunkAssembler(nchan=nchan, step=step, manifest=manifest)
    for pkt in [pkts[0]] + pkts[step // spp:]:
        asm.push(pkt)
    asm.close()
    got = drain(asm)
    assert 0 not in got and step in got
    led = asm.ledger
    assert led.quarantined == step and led.unaccounted() == 0
    assert [r["reason"] for r in led.journal] == [reasons.FEED_GAP]
    recs = manifest.records()
    assert len(recs) == 1 and recs[0]["reason"] == reasons.FEED_GAP


def test_assembler_duplicate_placed_once():
    nchan, step = 4, 64
    block = make_block(nchan, step, seed=6)
    pkts = packets_of(block, 16)
    asm = ChunkAssembler(nchan=nchan, step=step)
    for pkt in pkts:
        asm.push(pkt)
    assert asm.push(pkts[1]) == 0  # full duplicate: nothing placed
    asm.close()
    assert asm.duplicates == 1
    got = drain(asm)
    assert got[0].tobytes() == np.ascontiguousarray(block).tobytes()
    assert asm.ledger.observed == step and asm.ledger.unaccounted() == 0


def test_assembler_descending_wire_delivers_ascending():
    nchan, step = 4, 32
    ascending = make_block(nchan, step, seed=7)
    wire = ascending[::-1]  # what a descending-band backend ships
    asm = ChunkAssembler(nchan=nchan, step=step, band_descending=True)
    for pkt in packets_of(wire, 8, band_descending=True):
        asm.push(pkt)
    asm.close()
    got = drain(asm)
    assert got[0].tobytes() == np.ascontiguousarray(ascending).tobytes()


def test_assembler_geometry_mismatch_counts_invalid():
    asm = ChunkAssembler(nchan=8, step=64)
    other = packets_of(make_block(4, 16), 16)[0]  # wrong nchan
    assert asm.push(other) == 0
    assert asm.invalid == 1
    asm.close()
    assert asm.ledger.observed == 0


def test_assembler_shed_drops_oldest_journaled(tmp_path):
    from pulsarutils_tpu.faults.policy import QuarantineManifest

    nchan, step = 4, 64
    block = make_block(nchan, 4 * step, seed=8)
    manifest = QuarantineManifest(str(tmp_path), "ingest")
    asm = ChunkAssembler(nchan=nchan, step=step, shed=1,
                         manifest=manifest)
    for pkt in packets_of(block, step):  # nobody consuming
        asm.push(pkt)
    asm.close()
    got = drain(asm)
    # only the NEWEST chunk survives a bound of one
    assert sorted(got) == [3 * step]
    led = asm.ledger
    assert led.shed == 3 * step and led.delivered == step
    assert led.unaccounted() == 0
    shed_recs = [r for r in led.journal
                 if r["reason"] == reasons.SHED_OVERRUN]
    assert [r["chunk"] for r in shed_recs] == [0, step, 2 * step]
    assert [r["reason"] for r in manifest.records()] \
        == [reasons.SHED_OVERRUN] * 3


def test_assembler_push_never_blocks_on_wedged_consumer():
    """The bounded-time pin: a consumer that never drains cannot stall
    the reader side — every push returns promptly and sheds instead."""
    nchan, step = 4, 256
    block = make_block(nchan, 16 * step, seed=9)
    asm = ChunkAssembler(nchan=nchan, step=step, shed=2)
    t0 = time.monotonic()
    for pkt in packets_of(block, step):
        asm.push(pkt)
    asm.close()
    assert time.monotonic() - t0 < 5.0
    led = asm.ledger
    assert led.shed >= step  # pressure really shed chunks
    assert led.unaccounted(queued_samples=2 * step) == 0
    drain(asm)
    assert led.unaccounted() == 0


def test_assembler_far_future_packet_forces_cuts():
    nchan, step = 4, 64  # ring capacity = step + reorder_window
    asm = ChunkAssembler(nchan=nchan, step=step, reorder_window=64)
    tail = make_block(nchan, 16, seed=10)
    pkt = packets_of(tail, 16)[0]
    far = decode_packet(packetize_array(
        tail, samples_per_packet=16, sample0=8 * step)[0])[0]
    asm.push(pkt)
    asm.push(far)  # would lap the ring: forces cuts of the hole
    asm.close()
    drain(asm)
    led = asm.ledger
    assert led.observed == 8 * step + 16
    assert led.unaccounted() == 0
    assert led.quarantined > 0  # the hole quarantined as feed_gap
    assert all(r["reason"] == reasons.FEED_GAP for r in led.journal)


# -- socket sources -----------------------------------------------------------

def test_tcp_feed_lossless_byte_identity_with_disk_search(tmp_path):
    """A lossless localhost feed must reproduce the disk search byte for
    byte — delivered chunks, per-chunk tables, and the hit list."""
    from pulsarutils_tpu.io.sigproc import (FilterbankReader,
                                            write_simulated_filterbank)
    from pulsarutils_tpu.models.simulate import disperse_array
    from pulsarutils_tpu.parallel.stream import stream_search

    tsamp, nchan, step = 0.0005, 16, 1024
    nsamples = 3 * step
    rng = np.random.default_rng(23)
    arr = np.abs(rng.normal(0, 0.5, (nchan, nsamples))) + 20.0
    arr[:, step + step // 2] += 6.0
    arr = disperse_array(arr, 150.0, 1200., 200., tsamp)
    fname = str(tmp_path / "survey.fil")
    write_simulated_filterbank(
        fname, arr, {"bandwidth": 200., "fbottom": 1200.,
                     "nchans": nchan, "nsamples": nsamples,
                     "tsamp": tsamp, "foff": 200. / nchan},
        descending=True)

    reader = FilterbankReader(fname)
    wire = reader.read_block(0, nsamples).astype(np.float32)
    disk = reader.read_block(0, nsamples,
                             band_ascending=True).astype(np.float32)
    encoded = packetize_array(wire, samples_per_packet=128,
                              band_descending=reader.band_descending)

    asm = ChunkAssembler(nchan=nchan, step=step,
                         band_descending=reader.band_descending,
                         wait_poll_s=0.05)
    delivered = {}

    def consume():
        for istart, chunk in asm.chunks():
            delivered[istart] = np.asarray(chunk)

    consumer = threading.Thread(target=consume, daemon=True)
    consumer.start()
    with TCPSource(asm, port=0, max_reconnects=0) as src:
        feed_tcp(src.host, src.port, encoded)
        assert src.wait(timeout_s=30), "reader failed to drain"
    consumer.join(timeout=30)

    assert sorted(delivered) == [0, step, 2 * step]
    for s, chunk in delivered.items():
        assert chunk.tobytes() == \
            np.ascontiguousarray(disk[:, s:s + step]).tobytes()
    assert asm.ledger.unaccounted() == 0 and not asm.ledger.journal
    assert asm.invalid == 0 and asm.ledger.gap_filled == 0

    dms = np.linspace(100., 200., 16)
    args = (100., 200., 1200., 200., tsamp)
    res_disk, hits_disk = stream_search(
        [(s, np.ascontiguousarray(disk[:, s:s + step]))
         for s in (0, step, 2 * step)], *args, trial_dms=dms)
    res_feed, hits_feed = stream_search(
        sorted(delivered.items()), *args, trial_dms=dms)
    assert len(hits_disk) >= 1  # the injected pulse is really found
    assert [h[0] for h in hits_disk] == [h[0] for h in hits_feed]
    for (s1, t1), (s2, t2) in zip(res_disk, res_feed):
        assert s1 == s2
        for col in t1.colnames:
            assert np.asarray(t1[col]).tobytes() \
                == np.asarray(t2[col]).tobytes(), (s1, col)


def test_tcp_corrupt_packet_surfaces_as_gap():
    nchan, step = 4, 64
    block = make_block(nchan, step, seed=11)
    encoded = packetize_array(block, samples_per_packet=16)
    hurt = bytearray(encoded[1])
    hurt[HEADER_SIZE] ^= 0xFF
    encoded[1] = bytes(hurt)

    asm = ChunkAssembler(nchan=nchan, step=step)
    with TCPSource(asm, port=0, max_reconnects=0) as src:
        feed_tcp(src.host, src.port, encoded)
        assert src.wait(timeout_s=30)
    got = drain(asm)
    assert asm.invalid == 1
    expected = block.copy()
    expected[:, 16:32] = 0.0
    assert got[0].tobytes() == np.ascontiguousarray(expected).tobytes()
    assert asm.ledger.gap_filled == 16
    assert asm.ledger.unaccounted() == 0


def test_tcp_idle_timeout_ends_session():
    nchan, step = 4, 32
    block = make_block(nchan, step, seed=12)
    asm = ChunkAssembler(nchan=nchan, step=step, wait_poll_s=0.05)
    got = {}

    def consume():
        for istart, chunk in asm.chunks():
            got[istart] = np.asarray(chunk)

    consumer = threading.Thread(target=consume, daemon=True)
    consumer.start()
    with TCPSource(asm, port=0, idle_timeout_s=0.3) as src:
        feed_tcp(src.host, src.port,
                 packetize_array(block, samples_per_packet=16))
        # no close() from this side: the idle reader must flush
        assert src.wait(timeout_s=30)
        consumer.join(timeout=30)
        assert not consumer.is_alive(), "iterator never terminated"
    assert sorted(got) == [0]
    assert asm.ledger.delivered == step


def test_tcp_idle_timeout_fires_with_no_connection_at_all():
    # the idle clock runs from session start: a listener whose feeder
    # never connects must still drain, not wait forever
    asm = ChunkAssembler(nchan=4, step=16, wait_poll_s=0.05)
    with TCPSource(asm, port=0, idle_timeout_s=0.3) as src:
        assert src.wait(timeout_s=10), "idle listener never exited"
    assert list(asm.chunks()) == []
    assert asm.ledger.observed == 0


def test_udp_feed_localhost_roundtrip():
    nchan, step = 4, 64
    block = make_block(nchan, step, seed=13)
    asm = ChunkAssembler(nchan=nchan, step=step)
    with UDPSource(asm, port=0, idle_timeout_s=0.3) as src:
        feed_udp(src.host, src.port,
                 packetize_array(block, samples_per_packet=16),
                 pace_s=0.002)
        assert src.wait(timeout_s=30)
    got = drain(asm)
    led = asm.ledger
    assert led.unaccounted() == 0
    # loopback datagrams are reliable at this size in practice; if the
    # kernel sheds one anyway the ledger must still balance exactly
    assert led.arrived + led.gap_filled == led.observed
    if led.gap_filled == 0:
        assert got[0].tobytes() == np.ascontiguousarray(block).tobytes()


def test_tcp_reconnect_is_counted():
    nchan, step = 4, 64
    block = make_block(nchan, 2 * step, seed=14)
    encoded = packetize_array(block, samples_per_packet=32)
    asm = ChunkAssembler(nchan=nchan, step=step)
    with TCPSource(asm, port=0, idle_timeout_s=0.4,
                   backoff_s=0.01) as src:
        feed_tcp(src.host, src.port, encoded[:2])
        feed_tcp(src.host, src.port, encoded[2:])  # second connection
        assert src.wait(timeout_s=30)
    got = drain(asm)
    assert asm.reconnects == 1
    for s in (0, step):
        assert got[s].tobytes() == \
            np.ascontiguousarray(block[:, s:s + step]).tobytes()
    assert asm.ledger.unaccounted() == 0


# -- HealthEngine ingest conditions (satellite 3) -----------------------------

def test_health_feed_gap_degrades_then_decays():
    eng = HealthEngine(recover_after=2)
    assert eng.update(0, ingest_gap_frac=0.25) == DEGRADED
    assert "feed_gap" in eng.reasons()
    assert eng.update(1, ingest_gap_frac=0.0) == DEGRADED  # ttl 1 left
    assert eng.update(2, ingest_gap_frac=0.0) == OK
    assert eng.reasons() == []


def test_health_sustained_overrun_escalates_to_critical():
    eng = HealthEngine(recover_after=1, overrun_critical_after=3)
    assert eng.update(0, ingest_overrun=1) == DEGRADED
    assert eng.update(1, ingest_overrun=2) == DEGRADED
    assert eng.update(2, ingest_overrun=1) == CRITICAL  # 3rd in a row
    assert "feed_overrun" in eng.reasons()
    # pressure lifts: one clean chunk breaks the run, decay follows
    assert eng.update(3) == OK
    # a lone later overrun is only DEGRADED again (run restarted)
    assert eng.update(4, ingest_overrun=1) == DEGRADED


def test_health_disconnect_recovers_within_recover_after():
    eng = HealthEngine(recover_after=2)
    assert eng.update(0, ingest_disconnects=1) == DEGRADED
    assert "feed_disconnect" in eng.reasons()
    verdicts = [eng.update(i) for i in (1, 2)]
    assert verdicts[-1] == OK


def test_assembler_feeds_health_conditions():
    eng = HealthEngine(recover_after=1, gap_degraded=0.0)
    nchan, step, spp = 4, 64, 16
    block = make_block(nchan, 2 * step, seed=15)
    pkts = packets_of(block, spp)
    del pkts[1]  # one lost packet in chunk 0
    asm = ChunkAssembler(nchan=nchan, step=step, health=eng)
    for pkt in pkts:
        asm.push(pkt)
    asm.close()
    assert eng.verdict == OK  # clean chunk 1 decayed the gap flag
    kinds = [i for i in eng.snapshot()["incidents"]]
    assert any("feed_gap" in str(i) for i in kinds)


# -- bounded lookahead (satellite 1) ------------------------------------------

def test_iter_lookahead_is_bounded_and_order_preserving():
    from pulsarutils_tpu.parallel.stream import _iter_lookahead

    produced, consumed = [], []

    def gen():
        for i in range(10):
            produced.append(i)
            yield i

    for item in _iter_lookahead(gen()):
        consumed.append(item)
        # at most the pending slot + one in-flight next
        assert len(produced) - len(consumed) <= 2
    assert consumed == list(range(10))
    assert _iter_lookahead(iter([])) is not None
    assert list(_iter_lookahead(iter([]))) == []


def test_stream_search_generator_matches_list_and_stays_lazy():
    """A generator producer gives byte-identical results to the same
    chunks as a list, and is never pulled more than one chunk past the
    chunk being searched (bounded memory for a live feed)."""
    from pulsarutils_tpu.parallel.stream import stream_search

    nchan, step, n = 8, 512, 4
    block = make_block(nchan, n * step, seed=16)
    chunk_list = [(s, np.ascontiguousarray(block[:, s:s + step]))
                  for s in range(0, n * step, step)]
    state = {"produced": 0, "searched": 0, "max_ahead": 0}

    def producer():
        for item in chunk_list:
            state["produced"] += 1
            state["max_ahead"] = max(
                state["max_ahead"],
                state["produced"] - state["searched"])
            yield item

    def saw_plane(istart, plane, table):
        state["searched"] += 1

    args = (100., 200., 1200., 200., 0.0005)
    dms = np.linspace(100., 200., 8)
    res_gen, hits_gen = stream_search(producer(), *args, trial_dms=dms,
                                      plane_consumer=saw_plane)
    res_list, hits_list = stream_search(
        chunk_list, *args, trial_dms=dms,
        plane_consumer=lambda *a: None)
    assert state["produced"] == n
    assert state["max_ahead"] <= 2
    assert [h[0] for h in hits_gen] == [h[0] for h in hits_list]
    for (s1, t1), (s2, t2) in zip(res_gen, res_list):
        assert s1 == s2
        for col in t1.colnames:
            assert np.asarray(t1[col]).tobytes() \
                == np.asarray(t2[col]).tobytes()
