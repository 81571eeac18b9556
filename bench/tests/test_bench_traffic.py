"""The traffic generator: deterministic per seed, inside its bounds, the
same sizes for every seed, and the sessions' bookkeeping."""

import hashlib
import json
from collections import deque

import numpy as np
import pytest

from bench import spec, traffic
from bench_tiny import DATA

MIXES = sorted(p.stem for p in (spec.ROOT / "bench" / "traffic").glob("*.json"))
TINY = ["tiny_sessions", "tiny_sessions_closed"]


def _spec(name):
    path = spec.traffic_path(name)
    if not path.exists():
        path = DATA / f"{name}.json"
    return json.loads(path.read_text())


def _drive(name, seed, n=40):
    """The mix's first ``n`` jobs as the harness would see them, each
    completed, oldest first, as soon as it is released."""
    mix = traffic.make_mix(_spec(name), seed, vocab=1000)
    out = list(mix.setup_jobs())
    pending = deque(mix.first_jobs())
    out += pending
    t = 0.0
    while len(out) < n:
        t += 0.25
        new = mix.due_jobs(t)
        if pending:
            job = pending.popleft()
            new += mix.on_done(job, job.prompt, list(range(1, job.max_new + 1)), t)
        pending.extend(new)
        out.extend(new)
    return out[:n]


def _sig(jobs):
    return [(len(j.prompt), j.max_new, j.tag, j.due,
             int(j.prompt.sum())) for j in jobs]


@pytest.mark.parametrize("name", MIXES + TINY)
def test_deterministic_per_seed(name):
    assert _sig(_drive(name, 2 ** 33 + 5)) == _sig(_drive(name, 2 ** 33 + 5))
    assert _sig(_drive(name, 2 ** 33 + 5)) != _sig(_drive(name, 11))


@pytest.mark.parametrize("name", MIXES + TINY)
def test_lengths_within_bounds(name):
    sp = _spec(name)
    for job in _drive(name, 3, n=80):
        assert job.prompt.dtype == np.int32 and job.prompt.min() >= 1
        assert job.max_new >= 1
        assert len(job.prompt) + job.max_new <= (
            sp["max_tokens"] + sp["user"]["max"] + sp["answer"]["max"])


@pytest.mark.parametrize("dist", [
    {"dist": "lognormal", "median": 256, "sigma": 0.6, "min": 32, "max": 512},
    {"dist": "uniform", "min": 16, "max": 2048},
    {"dist": "exponential", "mean": 0.5}])
def test_every_seed_draws_the_same_sizes(dist):
    a = traffic.stratified(dist, np.random.default_rng(1))
    b = traffic.stratified(dist, np.random.default_rng(2))
    assert sorted(a) == sorted(b) and list(a) != list(b)
    assert a.min() >= dist.get("min", 0) and a.max() <= dist.get("max", 1e9)


def test_sessions_turns_go_to_idle_sessions_and_end():
    sp = _spec("tiny_sessions")
    mix = traffic.make_mix(sp, 5, vocab=1000)
    setup = mix.setup_jobs()
    assert sorted(j.tag for j in setup) == list(range(sp["sessions"]))
    busy = {}
    t, fresh = 0.0, 0
    for _ in range(300):
        t += 0.05
        for job in mix.due_jobs(t):
            assert job.tag not in busy       # a turn goes to an idle session
            busy[job.tag] = job
            fresh += mix.turns[job.tag] == 0
        if busy:
            job = busy.pop(min(busy))
            mix.on_done(job, job.prompt, list(range(1, job.max_new + 1)), t)
            assert mix.turns[job.tag] <= sp["max_turns"]
            assert len(mix.history[job.tag]) <= sp["max_tokens"]
    assert fresh > 0                         # ended sessions were replaced


def test_closed_sessions_send_the_next_turn_on_completion():
    sp = _spec("tiny_sessions_closed")
    mix = traffic.make_mix(sp, 2 ** 33 + 1, vocab=1000)
    mix.setup_jobs()
    first = mix.first_jobs()
    assert sorted(j.tag for j in first) == list(range(sp["sessions"]))
    assert all(j.due is None for j in first) and mix.due_jobs(1e9) == []
    job = first[0]
    out = list(range(1, job.max_new + 1))
    nxt, = mix.on_done(job, job.prompt, out, 1.0)
    assert nxt.tag == job.tag and nxt.due is None
    hist = np.concatenate([job.prompt, np.asarray(out, np.int32)])
    if mix.turns[job.tag] > 0:          # not replaced by a fresh session
        assert np.array_equal(nxt.prompt[:len(hist)], hist)


@pytest.mark.parametrize("name", MIXES + TINY)
@pytest.mark.parametrize("key", ["user", "answer"])
def test_lognormal_lengths_keep_their_median(name, key):
    """A block of 64 draws straddles the stated median, half on each
    side, so the mix sends the distribution its file states."""
    dist = _spec(name)[key]
    v = np.sort(traffic.stratified(dist, np.random.default_rng(0)))
    assert v[31] <= dist["median"] + 1 and v[32] >= dist["median"] - 1


def test_setup_turns_become_the_sessions_history():
    """Set-up's first turns carry a drawn answer in their prompt and
    decode two tokens; each becomes its session's history."""
    sp = _spec("tiny_sessions_closed")
    mix = traffic.make_mix(sp, 2 ** 33 + 3, vocab=1000)
    for job in mix.setup_jobs():
        assert job.max_new == 2 and job.due is None
        assert mix.history[job.tag] is job.prompt
        assert mix.turns[job.tag] == 1
        assert any(len(job.prompt) > len(sys_prompt)
                   and np.array_equal(job.prompt[:len(sys_prompt)], sys_prompt)
                   for sys_prompt in mix.system)


def test_sessions_closed_draws_the_jobs_it_drew_before():
    """The sessions mix's first 80 jobs for one seed, pinned when the
    generator learned one-turn sessions (``data/tiny_pins.json``)."""
    pin = json.loads((DATA / "tiny_pins.json").read_text())["sessions_closed"]
    sig = [(len(j.prompt), j.max_new, j.tag, j.due,
            hashlib.sha256(j.prompt.tobytes()).hexdigest()[:16])
           for j in _drive("sessions_closed", pin["seed"], n=pin["jobs"])]
    assert hashlib.sha256(json.dumps(sig).encode()).hexdigest() \
        == pin["sha256"]


def test_decode_closed_draws_the_same_lengths_for_every_seed():
    """Every seed sends the same lengths in the same order, the first
    wave and the requests after it; only the tokens differ."""
    runs = [_drive("decode_closed", seed, n=16 + 64)
            for seed in (2 ** 33 + 5, 3 * 2 ** 31 + 1)]
    size = lambda j: (len(j.prompt), j.max_new, j.tag)
    assert [size(j) for j in runs[0]] == [size(j) for j in runs[1]]
    assert not any(np.array_equal(a.prompt, b.prompt) for a, b in zip(*runs))
    assert len({j.max_new for j in runs[0][16:]}) > 20
    assert all(j.due is None for j in runs[0])


def test_decode_closed_opens_part_way_through_answers():
    """Each first request carries the part of its answer already served
    in its prompt and asks for the rest: prompt and remainder add up to a
    user message (of the 16 quantiles) and an answer (of the 64) of the
    mix's distributions.  Long answers,
    which hold their slots longer, are met more often than they are
    drawn."""
    sp = _spec("decode_closed")
    quant = lambda key, n=64: traffic.stratified(
        sp[key], np.random.default_rng(0), n=n).tolist()
    users, answers = set(quant("user", sp["sessions"])), quant("answer")
    first = traffic.make_mix(sp, 2 ** 33 + 11, vocab=1000).first_jobs()
    met = []
    for job in first:
        whole = len(job.prompt) + job.max_new
        found = [whole - u for u in users
                 if whole - u in answers and len(job.prompt) >= u]
        assert found and job.max_new >= 1
        met.append(min(found))
    assert sorted(j.tag for j in first) == list(range(sp["sessions"]))
    assert np.mean(met) > 1.5 * np.mean(answers)
    assert max(len(j.prompt) for j in first) > max(users)


def test_decode_closed_shares_no_page_of_prompt():
    """Random prompts, no system prompt and one turn a session: no two
    requests begin with the same page of tokens."""
    jobs = _drive("decode_closed", 2 ** 33 + 9, n=200)
    firsts = {j.prompt[:64].tobytes() for j in jobs}
    assert len(firsts) == len(jobs)
    assert traffic.make_mix(_spec("decode_closed"), 1, 1000).setup_jobs() \
        == []
