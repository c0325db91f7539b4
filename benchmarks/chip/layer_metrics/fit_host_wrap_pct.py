"""Host work wrapped around the fit programs, as a share of the job:
(``stack`` + ``h2d`` + ``init`` + ``collect`` of the ``cv_train`` and
``final_fit`` phases' parts, ``build_status.json``) / job seconds (host
clock around the command); median over the window's jobs. ``stack`` is
the host copy of a bucket's members into one block, ``h2d`` the
``device_put`` dispatch, ``init`` the parameter and optimizer-state
programs, ``collect`` the fetch and unstacking of the results; the fit
program itself is the ``device_program`` span and is not in it. None
where the program records no parts."""

from harness.stats import median

PHASES = ("cv_train", "final_fit")
PARTS = ("stack", "h2d", "init", "collect")


def read(evidence):
    shares = []
    for job in evidence["jobs"]:
        phases = (job.get("status") or {}).get("phases") or {}
        parts = [phases[p]["parts"] for p in PHASES if (phases.get(p) or {}).get("parts")]
        if not parts:
            return None
        seconds = sum(found[p]["seconds"] for found in parts for p in PARTS if p in found)
        shares.append(100.0 * seconds / job["seconds"])
    return median(shares)
