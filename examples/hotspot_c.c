/* Hotspot at native scale: the BASELINE.json scenario shape (all work
 * enters one server, consumers spread everywhere — the situation
 * cross-server balancing exists for; compare the reference's skel.c
 * synthetic stress shape, reference examples/skel.c:10-40) driven
 * entirely by native processes: C clients (this file) against the C++
 * server daemons, with the JAX balancer sidecar planning in tpu mode.
 *
 * Rank 0 produces ADLB_HOT_NTASKS tokens; with ADLB_PUT_ROUTING=home they
 * all land on rank 0's home server. Every other rank consumes with
 * ADLB_HOT_WORK_US of usleep "compute" per token. Each worker prints one
 * machine-readable line:
 *
 *   HOT done=<n> busy=<secs> t0=<mono> t1=<mono>
 *
 * (CLOCK_MONOTONIC is system-wide on Linux, so the harness can take
 * cross-process makespans.) The producer prints HOT done=0 ... with its
 * first-put timestamp. Termination is by exhaustion.
 */
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include <time.h>
#include <unistd.h>

#include <adlb/adlb.h>

#define TOKEN 1

static double mono(void) {
  struct timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return (double)ts.tv_sec + 1e-9 * (double)ts.tv_nsec;
}

int main(void) {
  int types[1] = {TOKEN};
  int am_server = -1, am_debug = -1, num_apps = 0;
  const char *nsrv_env = getenv("ADLB_NUM_SERVERS");
  int nservers = nsrv_env ? atoi(nsrv_env) : 0; /* <= 0 is rejected by ADLB_Init */
  int n_tasks = getenv("ADLB_HOT_NTASKS") ? atoi(getenv("ADLB_HOT_NTASKS")) : 200;
  int work_us = getenv("ADLB_HOT_WORK_US") ? atoi(getenv("ADLB_HOT_WORK_US")) : 2000;
  int rc = ADLB_Init(nservers, 0, 0, 1, types, &am_server, &am_debug,
                     &num_apps);
  if (rc != ADLB_SUCCESS || am_server || am_debug) {
    fprintf(stderr, "hotspot: init failed rc=%d\n", rc);
    return 2;
  }
  int me = ADLB_World_rank();

  if (me == 0) {
    /* pure producer, like the Python hotspot: put everything, then leave;
     * workers terminate by exhaustion once the pool drains */
    double t0 = mono();
    for (int i = 0; i < n_tasks; i++) {
      rc = ADLB_Put("w", 1, -1, -1, TOKEN, 0);
      if (rc != ADLB_SUCCESS) {
        fprintf(stderr, "hotspot: put %d failed rc=%d\n", i, rc);
        return 3;
      }
    }
    printf("HOT done=0 busy=0.000000 t0=%.6f t1=%.6f\n", t0, t0);
    ADLB_Finalize();
    return 0;
  }

  int req[2] = {TOKEN, ADLB_RESERVE_EOL};
  int wt, wp, wl, ar;
  int done = 0;
  double wait = 0.0;
  double t0 = mono(), t1 = t0;
  /* wait = time blocked acquiring work, the steal-to-exec quantity;
   * "busy" is reported as NOMINAL compute (done * work_us) because on
   * an oversubscribed host the wall time of usleep includes
   * involuntary scheduler delay — a wall-clock busy measure inflates
   * utilization in exactly the runs where the kernel scheduler, not
   * balancing, is the bottleneck, making idle% move against
   * throughput. Default consumption uses the fused ADLB_Get_work (one
   * round trip when the unit is LOCAL to the home server): both modes
   * issue the identical call, so the mode that pre-positions work
   * locally is paid for that locality — the quantity this scenario
   * measures.  ADLB_HOT_FETCH=batch:<k> switches to the batched fused
   * fetch (up to k local units per round trip) so the bench can state
   * the measured single-vs-batch delta on this plane (single-unit
   * stays the default). */
  int batch = 0;
  const char *fetch_env = getenv("ADLB_HOT_FETCH");
  if (fetch_env && strncmp(fetch_env, "batch", 5) == 0) {
    /* only "batch" (default k=8) or "batch:<k>" — anything else,
     * trailing junk included, is rejected, never silently remapped:
     * the bench records the delta under the REQUESTED k */
    if (fetch_env[5] == ':') {
      char *end = NULL;
      long k = strtol(fetch_env + 6, &end, 10);
      if (!end || *end != '\0' || end == fetch_env + 6) return 4;
      batch = (int)k;
    } else if (fetch_env[5] == '\0') {
      batch = 8;
    } else {
      return 4;
    }
    if (batch < 1 || batch > 64) return 4;
  } else if (fetch_env && strcmp(fetch_env, "single") != 0) {
    return 4;
  }
  long rts = 0; /* fetch round trips: under batching, rts < done when any
                 * batch carried >1 unit — the realized amortization */
  if (batch) {
    int wts[64], wps[64], wls[64], ars[64], ngot;
    char bufs[64 * 8];
    for (;;) {
      double r0 = mono();
      rc = ADLB_Get_work_batch(req, batch, &ngot, wts, wps, bufs, 8, wls,
                               ars);
      if (rc != ADLB_SUCCESS) break; /* NO_MORE_WORK / EXHAUSTION */
      wait += mono() - r0;
      rts++;
      for (int i = 0; i < ngot; i++) {
        usleep((useconds_t)work_us);
        done++;
        t1 = mono();
      }
    }
  } else {
    for (;;) {
      char buf[8];
      double r0 = mono();
      rc = ADLB_Get_work(req, &wt, &wp, buf, (int)sizeof buf, &wl, &ar);
      if (rc != ADLB_SUCCESS) break; /* NO_MORE_WORK / DONE_BY_EXHAUSTION */
      wait += mono() - r0;
      rts++;
      usleep((useconds_t)work_us);
      done++;
      t1 = mono();
    }
  }
  double busy = (double)done * (double)work_us * 1e-6;
  printf("HOT done=%d busy=%.6f t0=%.6f t1=%.6f wait=%.6f fetch=%s rts=%ld\n",
         done, busy, t0, t1, wait, batch ? "batch" : "single", rts);
  ADLB_Finalize();
  return 0;
}
