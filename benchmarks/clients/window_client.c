/* The benchmark's native traffic client: one producer, N-1 workers, and a
 * measured window that the units themselves carry (benchmarks/README.md,
 * "The window"). Grown from examples/hotspot_c.c (reference shape:
 * kc9jud/adlb examples/coinop.cpp, one producer floods tokens and every
 * worker times every pop; examples/skel.c:10-40 per-unit delay).
 *
 * Rank 0 reads the unit plan the traffic generator wrote
 * (ADLB_WIN_UNITS: records of {int64 id, double due_s, int32 work_us,
 * uint32 tag}) and puts one unit per record, each no earlier than its
 * due offset — with ADLB_Put, or pipelined (ADLB_Iput, acknowledged at an
 * ADLB_Flush_puts every ADLB_WIN_FLUSH_EVERY puts) where the mix says so. At its first put it fixes t_end = now + ADLB_WIN_WARM_S +
 * ADLB_WIN_SECONDS and writes it into every payload:
 *
 *   payload = {int64 id, double t_put, double t_end, int32 work_us,
 *              uint32 tag}                                  (32 bytes)
 *
 * Every other rank fetches (ADLB_Get_work_batch with ADLB_WIN_FETCH units
 * at most, or ADLB_Get_work when that is 1) until the pool is exhausted.
 * A unit costs usleep(work_us) only while now < t_end; after t_end units
 * cost nothing, so the backlog drains and the world ends by exhaustion
 * with every unit delivered. Nothing is killed or signalled.
 *
 * Logs go to files of the rank's own under ADLB_WIN_LOGDIR, binary, fixed
 * records, buffered (never through the stdout pipe):
 *
 *   p0.start      {double t_first, double t_end}     at the first put
 *   p0.bin        {int64 n_acked, double t_first, double t_last,
 *                  double t_end}                      producer, one record
 *   p0.puts       {double put_s}   one per acknowledged ADLB_Put, in put
 *                  order: the seconds the call took. Synchronous mixes
 *                  only (ADLB_WIN_FLUSH_EVERY 0); a pipelined producer
 *                  writes no such file
 *   w<rank>.fetch {double t_call, double t_ret, int32 n_got, int32 rc}
 *   w<rank>.units {payload as received (32 bytes), double t_call,
 *                  double t_ret, double t_done}                (56 bytes)
 *
 * Times are CLOCK_MONOTONIC, system-wide on Linux. Exit code 0 only when
 * every put was acknowledged (producer) or the last fetch said the pool
 * is exhausted (worker).
 */
#include <stdint.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include <time.h>
#include <unistd.h>

#include <adlb/adlb.h>

#define TOKEN 1
#define MAX_BATCH 64

typedef struct {
  int64_t id;
  double due_s;
  int32_t work_us;
  uint32_t tag;
} unit_plan;

typedef struct {
  int64_t id;
  double t_put;
  double t_end;
  int32_t work_us;
  uint32_t tag;
} unit_payload;

typedef struct {
  double t_call, t_ret;
  int32_t n_got, rc;
} fetch_rec;

typedef struct {
  unit_payload p;
  double t_call, t_ret, t_done;
} unit_rec;

static double mono(void) {
  struct timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return (double)ts.tv_sec + 1e-9 * (double)ts.tv_nsec;
}

static void sleep_until(double t) {
  for (;;) {
    double left = t - mono();
    if (left <= 0) return;
    usleep((useconds_t)(left * 1e6 > 1000 ? 1000 : left * 1e6 + 1));
  }
}

static const char *need_env(const char *name) {
  const char *v = getenv(name);
  if (!v || !*v) {
    fprintf(stderr, "window_client: %s is not set\n", name);
    exit(5);
  }
  return v;
}

static FILE *open_log(const char *dir, const char *fmt, int rank) {
  char path[4096];
  char name[64];
  snprintf(name, sizeof name, fmt, rank);
  snprintf(path, sizeof path, "%s/%s", dir, name);
  FILE *f = fopen(path, "wb");
  if (!f) {
    fprintf(stderr, "window_client: cannot open %s\n", path);
    exit(5);
  }
  setvbuf(f, NULL, _IOFBF, 1 << 20);
  return f;
}

static int produce(const char *logdir) {
  const char *plan_path = need_env("ADLB_WIN_UNITS");
  double warm_s = atof(need_env("ADLB_WIN_WARM_S"));
  double seconds = atof(need_env("ADLB_WIN_SECONDS"));
  /* 0: synchronous ADLB_Put; k > 0: ADLB_Iput, flushed every k puts */
  long flush_every = atol(need_env("ADLB_WIN_FLUSH_EVERY"));
  FILE *pf = fopen(plan_path, "rb");
  if (!pf) {
    fprintf(stderr, "window_client: cannot read %s\n", plan_path);
    return 5;
  }
  fseek(pf, 0, SEEK_END);
  long bytes = ftell(pf);
  fseek(pf, 0, SEEK_SET);
  long n = bytes / (long)sizeof(unit_plan);
  unit_plan *plan = malloc((size_t)bytes + 1);
  if (!plan || fread(plan, sizeof(unit_plan), (size_t)n, pf) != (size_t)n) {
    fprintf(stderr, "window_client: short read of %s\n", plan_path);
    return 5;
  }
  fclose(pf);
  /* a synchronous producer keeps what every put took, by its own clock */
  double *put_s = NULL;
  if (flush_every <= 0 && !(put_s = malloc((size_t)(n + 1) * sizeof *put_s))) {
    fprintf(stderr, "window_client: no memory for %ld put times\n", n);
    return 5;
  }

  double t_first = mono(), t_last = t_first;
  double t_end = t_first + warm_s + seconds;
  /* the window's place in time, for whoever wants to trace inside it */
  FILE *sf = open_log(logdir, "p%d.start", 0);
  fwrite(&t_first, sizeof t_first, 1, sf);
  fwrite(&t_end, sizeof t_end, 1, sf);
  fclose(sf);
  int64_t acked = 0, in_flight = 0;
  for (long i = 0; i < n; i++) {
    if (plan[i].due_s > 0) sleep_until(t_first + plan[i].due_s);
    unit_payload p;
    p.id = plan[i].id;
    p.t_put = mono();
    p.t_end = t_end;
    p.work_us = plan[i].work_us;
    p.tag = plan[i].tag;
    int rc;
    if (flush_every > 0) {
      /* pipelined: the acknowledgements settle at the flush */
      rc = ADLB_Iput(&p, (int)sizeof p, -1, -1, TOKEN, 0);
      in_flight++;
      if (rc == ADLB_SUCCESS && (in_flight == flush_every || i == n - 1)) {
        rc = ADLB_Flush_puts();
        if (rc == ADLB_SUCCESS) acked += in_flight;
        in_flight = 0;
      }
    } else {
      rc = ADLB_Put(&p, (int)sizeof p, -1, -1, TOKEN, 0);
      if (rc == ADLB_SUCCESS) put_s[acked++] = mono() - p.t_put;
    }
    if (rc != ADLB_SUCCESS) {
      fprintf(stderr, "window_client: put %ld failed rc=%d\n", i, rc);
      return 3;
    }
    t_last = mono();
  }
  if (put_s) {
    FILE *qf = open_log(logdir, "p%d.puts", 0);
    fwrite(put_s, sizeof *put_s, (size_t)acked, qf);
    fclose(qf);
  }
  FILE *lf = open_log(logdir, "p%d.bin", 0);
  fwrite(&acked, sizeof acked, 1, lf);
  fwrite(&t_first, sizeof t_first, 1, lf);
  fwrite(&t_last, sizeof t_last, 1, lf);
  fwrite(&t_end, sizeof t_end, 1, lf);
  fclose(lf);
  printf("WIN producer acked=%lld put_s=%.3f\n", (long long)acked,
         t_last - t_first);
  ADLB_Finalize();
  return 0;
}

static int consume(const char *logdir, int me) {
  int batch = atoi(need_env("ADLB_WIN_FETCH"));
  if (batch < 1 || batch > MAX_BATCH) {
    fprintf(stderr, "window_client: ADLB_WIN_FETCH %d out of 1..%d\n", batch,
            MAX_BATCH);
    return 4;
  }
  FILE *ff = open_log(logdir, "w%d.fetch", me);
  FILE *uf = open_log(logdir, "w%d.units", me);
  int req[2] = {TOKEN, ADLB_RESERVE_EOL};
  int wts[MAX_BATCH], wps[MAX_BATCH], wls[MAX_BATCH], ars[MAX_BATCH];
  unit_payload bufs[MAX_BATCH];
  long done = 0;
  int rc;
  for (;;) {
    int ngot = 0;
    fetch_rec fr;
    fr.t_call = mono();
    if (batch > 1) {
      rc = ADLB_Get_work_batch(req, batch, &ngot, wts, wps, bufs,
                               (int)sizeof(unit_payload), wls, ars);
    } else {
      rc = ADLB_Get_work(req, &wts[0], &wps[0], bufs,
                         (int)sizeof(unit_payload), &wls[0], &ars[0]);
      ngot = rc == ADLB_SUCCESS ? 1 : 0;
    }
    fr.t_ret = mono();
    fr.n_got = rc == ADLB_SUCCESS ? ngot : 0;
    fr.rc = rc;
    fwrite(&fr, sizeof fr, 1, ff);
    if (rc != ADLB_SUCCESS) break; /* NO_MORE_WORK / DONE_BY_EXHAUSTION */
    for (int i = 0; i < ngot; i++) {
      unit_rec ur;
      memset(&ur, 0, sizeof ur);
      if (wls[i] == (int)sizeof(unit_payload)) ur.p = bufs[i];
      else ur.p.id = -1; /* a payload of another length is an altered one */
      ur.t_call = fr.t_call;
      ur.t_ret = fr.t_ret;
      if (ur.p.work_us > 0 && mono() < ur.p.t_end)
        usleep((useconds_t)ur.p.work_us);
      ur.t_done = mono();
      fwrite(&ur, sizeof ur, 1, uf);
      done++;
    }
  }
  fclose(ff);
  fclose(uf);
  printf("WIN worker rank=%d done=%ld rc=%d\n", me, done, rc);
  ADLB_Finalize();
  return (rc == ADLB_DONE_BY_EXHAUSTION || rc == ADLB_NO_MORE_WORK) ? 0 : 6;
}

int main(void) {
  int types[1] = {TOKEN};
  int am_server = -1, am_debug = -1, num_apps = 0;
  const char *nsrv_env = getenv("ADLB_NUM_SERVERS");
  int nservers = nsrv_env ? atoi(nsrv_env) : 0;
  const char *logdir = need_env("ADLB_WIN_LOGDIR");
  int rc = ADLB_Init(nservers, 0, 0, 1, types, &am_server, &am_debug,
                     &num_apps);
  if (rc != ADLB_SUCCESS || am_server || am_debug) {
    fprintf(stderr, "window_client: init failed rc=%d\n", rc);
    return 2;
  }
  int me = ADLB_World_rank();
  return me == 0 ? produce(logdir) : consume(logdir, me);
}
