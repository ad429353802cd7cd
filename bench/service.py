"""The system under test, built from its public pieces for one cell.

The round step is ``repro.core.serve.make_round_step`` (donated), driven by
``repro.core.serve.serve_rounds``; the FL buffers, where the configuration
trains an FL model, come from ``repro.fl.stream.fl_init``. The initial state
holds the seed-made data of ``world.py``, laid out in the program's own
state types; the per-call inputs are ``traffic.py``'s keys and plans in the
program's key and plan types. This is the only module of the benchmark that imports the program.
"""
import numpy as np


class Service:
    """One configured stream: ``fresh_state(world)`` and ``call(state,
    keys, plan)`` -> ``(state, metrics)``, with device metrics as the
    program returns them (``plan`` None without FL)."""

    def __init__(self, cfg, traffic):
        from repro.core import serve
        from repro.core.comms import WirelessConfig
        from repro.core.consensus import ConsensusConfig
        from repro.core.faults import FaultConfig
        from repro.core.latency import LatencyParams
        from repro.core.marl.env import EnvConfig
        from repro.core.migration import MigrationConfig
        from repro.fl.stream import FLServeConfig

        policy = cfg["association"]
        if policy not in ("average", "factorized"):
            raise ValueError(f"association {policy!r}: 'average' (round-robin) "
                             f"or 'factorized' (the MADDPG actor)")
        if policy == "factorized" and (cfg["migration"] or traffic["join_rate"]
                                       or traffic["leave_rate"]
                                       or traffic["rounds_per_call"] != 1):
            # the check reads the policy's association from the state after
            # each round, which migration and churn rewrite
            raise ValueError("a policy cell runs one round a call, without "
                             "migration or churn")
        if (cfg["faults"] or {}).get("malicious_frac"):
            raise ValueError("malicious twins are not generated by this benchmark")
        self.cfg, self.traffic = cfg, traffic

        def opt(make, sub):
            return None if sub is None else make(**sub)

        self.env_cfg = EnvConfig(
            n_twins=cfg["capacity"], n_bs=cfg["n_bs"],
            wireless=WirelessConfig(n_bs=cfg["n_bs"], **cfg["wireless"]),
            lat=LatencyParams(**cfg["latency"]),
            bs_freqs_ghz=tuple(cfg["bs_freqs_ghz"]),
            **({"data_max": cfg["policy"]["obs_data_max"]} if policy == "factorized" else {}),
            migration=opt(MigrationConfig, cfg["migration"]),
            faults=opt(FaultConfig, cfg["faults"]),
            consensus=opt(ConsensusConfig, cfg["consensus"]))
        self.fl_cfg = None if cfg["model"] is None else FLServeConfig(
            model=cfg["model"], participants=traffic["participants"],
            local_iters=cfg["local_iters"], batch_size=cfg["batch_size"],
            lr=cfg["lr"], momentum=cfg["momentum"],
            weighted_global=cfg["weighted_global"], aggregator=cfg["aggregator"],
            verify=cfg["verify"], tolerance=cfg["verify_tolerance"],
            n_eval=cfg["n_eval"])
        self.serve_cfg = serve.ServeConfig(
            capacity=cfg["capacity"], join_rate=traffic["join_rate"],
            leave_rate=traffic["leave_rate"],
            policy=None if policy == "average" else policy, fl=self.fl_cfg)
        self.step = serve.make_round_step(self.env_cfg, self.serve_cfg)
        self._serve = serve

    def fresh_state(self, world):
        import jax.numpy as jnp

        from repro.core.consensus import ChainState
        from repro.core.marl.env import EnvState
        from repro.core.scenario import StreamKnobs
        from repro.fl import stream

        from world import freqs_hz, model_module

        cfg, ccfg = self.cfg, self.cfg["consensus"]
        n, m = cfg["capacity"], cfg["n_bs"]
        assoc = jnp.arange(n, dtype=jnp.int32) % m
        data = world["data_sizes"]
        chain = None
        if ccfg is not None:
            per_bs = jnp.zeros((m,), jnp.float32).at[assoc].add(data)
            chain = ChainState(
                stakes=ccfg["s_ini"] * per_bs / jnp.maximum(jnp.sum(per_bs), 1e-9),
                verdicts=jnp.ones((ccfg["history"], m), jnp.float32),
                rewards=jnp.zeros((ccfg["history"], m), jnp.float32),
                round=jnp.zeros((), jnp.int32))
        env = EnvState(freqs=freqs_hz(cfg), data_sizes=data, h_up=world["h_up"],
                       h_down=world["h_down"], dist=world["dist"], assoc=assoc,
                       t=jnp.int32(0), chain=chain)
        active = np.ones((n,), bool)
        fl = None
        if self.fl_cfg is not None:
            data_set = ((world["x"], world["y"]), (world["x_test"], world["y_test"]),
                        model_module(cfg).DATA_SET)
            frozen = {"frozen": world["frozen"]} if "frozen" in world else {}
            fl = stream.fl_init(self.fl_cfg, None, data_set, active,
                                params=world["params"], **frozen)
        k = world["knobs"]
        self.row = StreamKnobs(
            data_min=k["data_min"], data_max=k["data_max"], skew=k["skew"],
            straggler=k["straggler"], outage=k["outage"], byzantine=k["byzantine"],
            quorum=k["quorum"], block_size=k["block_size"])
        state = self._serve.ServeState(
            env=env, active=jnp.asarray(active), bad=world["bad"], byz=world["byz"],
            fl=fl, round=jnp.int32(0))
        if "actor" in world:
            import jax
            from repro.core.marl.ddpg import DDPGConfig

            pol = cfg["policy"]
            # the agent's critic, target networks and optimizer state come
            # from the program's own init; a served round reads the actor only
            state = self._serve.attach_policy(
                self.env_cfg, state, jax.random.PRNGKey(0),
                dcfg=DDPGConfig(hidden=tuple(pol["hidden"])),
                replay_capacity=pol["replay_capacity"])
            state = state._replace(agent=state.agent._replace(actor=world["actor"]))
        return state

    def call(self, state, keys, plan):
        """One ``serve_rounds`` call over the rounds of ``keys``/``plan``."""
        from repro.fl.stream import FLPlan

        serve = self._serve
        return serve.serve_rounds(
            self.env_cfg, self.serve_cfg, state, serve.RoundKeys(**keys), self.row,
            step=self.step, plan=None if plan is None else FLPlan(**plan))

    def materialize(self, metrics):
        """The call's metrics on the host (blocks until they are there)."""
        return self._serve.stack_metrics(metrics)

    @staticmethod
    def global_params(state):
        """The global FL model, on the host."""
        import jax
        return jax.tree_util.tree_map(np.asarray, state.fl.params)

    @staticmethod
    def association(state):
        """The (N,) association the state carries, on the host: with a
        policy and neither migration nor churn, the one the last round's
        policy chose."""
        return np.asarray(state.env.assoc)

    def compiled_text(self, state, gen):
        """The compiled step's HLO text at this cell's shapes (a persistent
        compile-cache hit after the window has run it)."""
        from repro.fl.stream import FLPlan, plan_row
        serve = self._serve
        keys, plan = gen.next()
        rk = serve.round_keys(serve.RoundKeys(**keys), 0)
        pr = None if plan is None else plan_row(FLPlan(**plan), 0)
        return self.step.lower(state, rk, self.row, pr).compile().as_text()
