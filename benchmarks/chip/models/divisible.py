"""The divisible-load task model (arXiv:1910.02803, §2): ``W`` units of work
start on processor 0, and a successful steal takes half of what its victim
has left. ``simulate`` is the plain reference and imports nothing of the
program."""
from __future__ import annotations

import numpy as np

import reference as ref
from reference import ACTIVE, ANS_FLIGHT, INF, REQ_FLIGHT

#: Configuration keys of this model beside the shared ones (``cell.py``).
KEYS = ("W_list", "lam_list")


def check(config: dict, traffic: dict) -> None:
    """Nothing beyond the shared checks: every W and λ is a row's own."""


def query_kwargs(config: dict) -> dict:
    return {}


def simulate(config: dict, row: ref.Row) -> dict:
    return simulate_divisible(config["p"], row.W, row.lam, row.seed,
                              theta_static=row.theta_static,
                              theta_comm=row.theta_comm, mwt=config["mwt"])


def simulate_divisible(p: int, W: int, lam: int, seed: int,
                       theta_static: int = 0, theta_comm: int = 0,
                       mwt: bool = False) -> dict:
    """Divisible load ``W`` on processor 0 at time 0; a thief gets half of
    its victim's remaining work when that exceeds the threshold and, under
    SWT, the victim's channel is free."""
    state = np.full(p, ACTIVE, np.int64)
    idle_at = np.zeros(p, np.int64)
    idle_at[0] = W
    ev_time = idle_at.copy()
    victim = np.zeros(p, np.int64)
    stolen = np.zeros(p, np.int64)
    busy_until = np.zeros(p, np.int64)
    rng = [ref.proc_seed(seed, i) for i in range(p)]
    idle_since = np.zeros(p, np.int64)
    executed = np.zeros(p, np.int64)
    executed[0] = W
    active = p
    n_events = n_requests = n_success = n_fail = total_idle = 0
    startup_end = makespan = -1
    done = False

    def steal(i, t):
        v, rng[i] = ref.victim(rng[i], i, p)
        victim[i] = v
        state[i] = REQ_FLIGHT
        ev_time[i] = t + lam

    while not done:
        i = int(np.argmin(ev_time))
        t = int(ev_time[i])
        if t >= INF:
            break
        n_events += 1
        st = state[i]
        if st == ACTIVE:                        # i runs out of work
            state[i] = REQ_FLIGHT
            active -= 1
            idle_since[i] = t
            rem = (int((idle_at[state == ACTIVE] - t).sum())
                   + int(stolen[state == ANS_FLIGHT].sum()))
            if rem == 0:
                done = True
                makespan = t
                total_idle += int((t - idle_since[state != ACTIVE]).sum())
                break
            steal(i, t)
        elif st == REQ_FLIGHT:                  # the request reaches v
            v = int(victim[i])
            w_v = int(idle_at[v] - t) if state[v] == ACTIVE else 0
            amt = w_v // 2
            ok = (amt >= 1 and w_v > theta_static + theta_comm * lam
                  and (mwt or t >= busy_until[v]))
            n_requests += 1
            if ok:
                n_success += 1
                idle_at[v] = t + w_v - amt
                ev_time[v] = idle_at[v]
                executed[v] -= amt
                busy_until[v] = t + lam
            else:
                n_fail += 1
                amt = 0
            stolen[i] = amt
            state[i] = ANS_FLIGHT
            ev_time[i] = t + lam
        else:                                   # the answer reaches i
            amt = int(stolen[i])
            if amt > 0:
                state[i] = ACTIVE
                idle_at[i] = ev_time[i] = t + amt
                stolen[i] = 0
                executed[i] += amt
                active += 1
                total_idle += t - int(idle_since[i])
                if active == p and startup_end < 0:
                    startup_end = t
            else:
                steal(i, t)
    return dict(makespan=makespan, n_events=n_events, n_requests=n_requests,
                n_success=n_success, n_fail=n_fail, total_idle=total_idle,
                startup_end=startup_end, overflow=not done,
                executed=executed)
