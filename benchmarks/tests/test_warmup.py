"""The programs a cell can reach, from the engine's rounding rules."""

from harness import lengths, manifest, warmup

ENGINE = {"page_size": 64, "max_len": 1024, "max_slots": 32, "steps_per_call": 8,
          "prompt_buckets": [16, 32, 64, 128, 256, 512, 1024]}


def test_rounding_rules():
    assert [warmup.horizon(n, ENGINE) for n in (1, 56, 57, 120, 121, 500, 505, 1016)] == \
        [1, 1, 2, 2, 4, 8, 16, 16]
    assert [warmup.prefill_bucket(n, ENGINE) for n in (1, 16, 17, 512, 513)] == \
        [16, 16, 32, 512, 1024]
    for h in (4, 8, 16):  # a landing prompt is the cell's own and stays for two chunks
        n, new = warmup.landing(h, ENGINE, 129, 512)
        assert 129 <= n <= 512 and new == 16
        assert warmup.horizon(n, ENGINE) == warmup.horizon(n + 8, ENGINE) == h
    # a horizon only growth reaches: the longest prompt, with tokens enough to get there
    assert warmup.landing(16, ENGINE, 129, 400) == (400, 112 + 16)
    assert warmup.horizon(400 + 104, ENGINE) == 8 and warmup.horizon(400 + 112, ENGINE) == 16


def test_doc_prefill_reaches_one_bucket_and_one_chunk_program():
    traffic = manifest.load_json(manifest.BENCH_DIR + "/traffic/doc-prefill.json")
    got = warmup.reachable(ENGINE, lengths.multiset(traffic), traffic["clients"],
                           traffic["warm_group_max"])
    assert got == {"prefill": {(1024, 1), (1024, 2), (1024, 4)}, "chunk": {((32, 16),)}}


def test_chat_waves_cover_their_targets_once():
    traffic = manifest.load_json(manifest.BENCH_DIR + "/traffic/chat-saturated.json")
    work = lengths.multiset(traffic)
    got = warmup.reachable(ENGINE, work, traffic["clients"], traffic["warm_group_max"])
    singles = {((32, h),) for h in (4, 8, 16)}
    assert got["chunk"] == singles | {((16, a), (16, b)) for a, b in ((4, 8), (4, 16), (8, 16))}
    assert got["prefill"] == {(b, k) for b in (256, 512) for k in (1, 2, 4, 8)}
    waves = warmup.waves(ENGINE, got, work)
    for bucket in (256, 512):  # every true group size, not only powers of two
        sizes = sorted(len(w["requests"]) for w in waves
                       if w["blocker"] and f"({bucket}," in w["for"])
        assert sizes == [1, 2, 3, 4, 5, 6, 7, 8]
    assert len(waves) == 16 + len(got["chunk"])
    assert all(p + a <= ENGINE["max_len"] for w in waves for p, a in w["requests"])


def test_the_server_log_is_read_back():
    log = ("WARNING jit compile: program=paged_prefill [bucket=128,k=2] signature=...\n"
           "WARNING jit compile: program=paged_chunk [steps=8,buckets=((32, 4),)] sig\n"
           "WARNING jit compile: program=paged_chunk [steps=8,buckets=((16, 2), (16, 16))] s\n")
    assert warmup.warmed(log) == {"prefill": {(128, 2)},
                                  "chunk": {((32, 4),), ((16, 2), (16, 16))}}
