"""The generators: same seed, same schedule; the seed reorders the work
and does not change it; phrase guesses reach the device, words do not."""

import asyncio

import numpy as np

from benchmarks.harness import traffic as tr

BIG = 2 ** 31 + 11


def test_same_seed_same_schedule_other_seed_other_order(guess_cell):
    PLAY = guess_cell.traffic
    a = tr.guess_schedule(PLAY, BIG, 10.0)
    b = tr.guess_schedule(PLAY, BIG, 10.0)
    c = tr.guess_schedule(PLAY, BIG + 1, 10.0)
    assert a == b and a != c


def test_every_seed_sends_the_same_titles_in_another_order(guess_cell):
    PLAY = guess_cell.traffic

    def sent(seed):
        return [tr.story_title(PLAY, seed, room, story)
                for story in range(17) for room in range(PLAY["rooms"])]

    assert sent(BIG) == sent(BIG) and sent(BIG) != sent(BIG + 1)
    assert sorted(sent(BIG)) == sorted(sent(BIG + 1))
    assert len(set(sent(BIG))) == 17


def test_every_seed_gets_the_same_work(guess_cell):
    PLAY = guess_cell.traffic
    _, a = tr.guess_schedule(PLAY, 1, 10.0)
    _, c = tr.guess_schedule(PLAY, 2, 10.0)
    assert len(a) == len(c) == 200
    assert sum(x[3] for x in a) == sum(x[3] for x in c) == 20
    gaps = lambda calls: np.sort(np.diff([0.0] + [x[0] for x in calls]))
    np.testing.assert_allclose(gaps(a), gaps(c), atol=1e-9)
    # Poisson at the stated rate: mean gap 1/rate
    assert abs(np.mean(gaps(a)) - 1 / 20) < 0.005
    phrases = [x[2] for x in a if x[3]]
    assert len(set(phrases)) == len(phrases)
    assert all(" " in p for p in phrases)
    assert all(" " not in x[2] for x in a if not x[3])


def test_percentile_is_nearest_rank():
    assert tr.percentile(range(1, 101), 95) == 95
    assert tr.percentile([5.0], 95) == 5.0
    assert tr.percentile([1, 2, 3, 4], 50) == 2


def test_phrases_miss_table_and_lru_words_do_not(guess_cell):
    """Through InferenceService.similarity at test size with a table over
    the words in play: device rows advance by one per unique phrase."""
    from cassmantle_tpu.config import test_config
    from cassmantle_tpu.ops.embed_table import EmbedTable
    from cassmantle_tpu.serving.service import InferenceService
    from cassmantle_tpu.utils.logging import metrics

    answers, calls = tr.guess_schedule(guess_cell.traffic, 7, 3.0)
    service = InferenceService(test_config())
    words = sorted({x[2] for x in calls if not x[3]}
                   | {w for room in answers for w in room})
    rng = np.random.RandomState(0)
    service.scorer.table = EmbedTable.from_embeddings(
        words, rng.randn(len(words), 64).astype(np.float32))

    async def drive():
        for _, room, guess, _ in calls:
            await service.similarity([(guess, a) for a in answers[room]])
        await service.stop()

    before = metrics.counter_total("scorer.embed_cache_misses")
    served = metrics.counter_total("overload.table_served")
    asyncio.run(drive())
    n_phrase = sum(x[3] for x in calls)
    assert n_phrase == 6
    assert metrics.counter_total("scorer.embed_cache_misses") - before \
        == n_phrase
    assert metrics.counter_total("overload.table_served") - served \
        == 2 * (len(calls) - n_phrase)
