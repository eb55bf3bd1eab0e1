"""The copied generator: the program's batches, reproducible from (seed, i)."""

from bench.traffic import generator as G

TRAFFIC = G.load("agentic_grpo")


def _sizes(trajs):
    return sorted((t.prompt_tokens, tuple(t.payload.gen_tokens),
                   tuple(t.payload.tool_output_tokens)) for t in trajs)


def test_same_seed_and_index_give_the_same_batch():
    a, pa = G.batch(TRAFFIC, 2 ** 33 + 7, 3)
    b, pb = G.batch(TRAFFIC, 2 ** 33 + 7, 3)
    assert pa == pb
    assert [(t.traj_id, t.prompt_id, t.sample_id, t.payload.gen_tokens) for t in a] \
        == [(t.traj_id, t.prompt_id, t.sample_id, t.payload.gen_tokens) for t in b]


def test_seeds_change_tokens_not_work():
    a, pa = G.batch(TRAFFIC, 1, 0)
    b, pb = G.batch(TRAFFIC, 2, 0)
    c, pc = G.batch(TRAFFIC, 1, 1)
    assert _sizes(a) == _sizes(b) == _sizes(c)
    assert [(t.traj_id, t.prompt_id, t.payload.gen_tokens) for t in a] \
        == [(t.traj_id, t.prompt_id, t.payload.gen_tokens) for t in b]
    assert pa != pb and pa != pc


def test_batch_keeps_grpo_groups():
    trajs, prompts = G.batch(TRAFFIC, 5, 0)
    assert len(trajs) == TRAFFIC["n_prompts"] * TRAFFIC["group_size"]
    for pid in range(TRAFFIC["n_prompts"]):
        group = [t for t in trajs if t.prompt_id == pid]
        assert len(group) == TRAFFIC["group_size"]
        assert len({tuple(prompts[t.traj_id]) for t in group}) == 1
    assert max(t.payload.total_tokens for t in trajs) <= TRAFFIC["max_total_tokens"] + 1
    assert G.required_capacity(trajs) <= G.capacity_bound(TRAFFIC)


def test_copy_matches_the_programs_generator():
    """Until the program's generator changes, the copy draws the same plans."""
    from repro.engine.runtime import miniaturize
    from repro.engine.workload import WorkloadConfig, generate

    mini = dict(max_steps=8, max_total_tokens=1024, max_prompt=256,
                max_tool_tokens=48, min_step_tokens=1)
    ours = G.miniaturize(G.generate("coding", 4, 4, 11, base_steps=3.0), **mini)
    theirs = miniaturize(generate(WorkloadConfig(task="coding", n_prompts=4,
                                                 group_size=4, seed=11,
                                                 base_steps=3.0)), **mini)
    for a, b in zip(ours, theirs):
        assert a.prompt_tokens == b.prompt_tokens
        assert a.payload.gen_tokens == b.payload.gen_tokens
        assert a.payload.tool_output_tokens == b.payload.tool_output_tokens
        assert a.payload.tool_failed == b.payload.tool_failed


def test_tool_tokens_are_what_the_environment_draws():
    from repro.engine.runtime import ToolEnvironment

    trajs, _ = G.batch(TRAFFIC, 9, 0)
    t = next(t for t in trajs if t.payload.num_steps > 1)
    env = ToolEnvironment(seed=1234)
    got = env.invoke(t, 0).output_tokens
    assert got == G.tool_tokens(1234, t.traj_id, 0, t.payload.tool_output_tokens[0])
