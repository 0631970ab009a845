// Native client library: the C API of include/adlb/adlb.h over the binary
// TLV wire codec (twin of adlb_tpu/runtime/codec.py — keep tables in sync).
//
// This is the native equivalent of the reference's client-side protocol
// engine (reference src/adlb.c:2638-3176): Put routing + reject/retry with
// least-loaded hints, blocking/non-blocking Reserve, Get_reserved with
// batch-common prefix fetch, batch puts, Info queries, finalize/abort —
// re-targeted from tagged MPI sends to the framework's TCP fabric.
//
// Threads: none of its own. The thread that blocks in a call does the reads:
// it looks at the listeners and the inbound connections with poll(), accepts,
// reads and decodes what arrives and returns the frame it waited for
// (poll_inbound). Between two native ranks of one host the frames travel
// through a ring in shared memory and the Unix-domain socket carries only
// wake-ups and the peer's death (hostsock.hpp): a look at such a
// connection is a read of its ring's tail, and this rank is sent a wake-up
// only while it is marked asleep in poll(). A call that awaits the answer
// to its own request looks without blocking for a bounded time first
// (hostsock::poll_budget_s: the answer is as a rule microseconds away, and
// a wake-up costs more; while the answers come through a ring the looks
// are memory reads and make no system call) and sleeps in poll() only when
// nothing came; every other wait sleeps at once
// (wait_for). Inbound traffic therefore makes progress only inside
// library calls, as the reference's client makes none outside MPI calls:
// between calls an abort, a pipelined put's response or an app message waits
// in the kernel's socket buffers. The API is strictly request/response like
// the reference's client (blocking MPI_Wait) and is not thread-safe.
// Little-endian hosts assumed (as is the Python struct '<' side).

#include <arpa/inet.h>
#include <netdb.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <sys/socket.h>
#include <unistd.h>

#include <algorithm>
#include <cerrno>
#include <cstdarg>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <ctime>
#include <deque>
#include <map>
#include <string>
#include <string_view>
#include <vector>

#include "../../include/adlb/adlb.h"
#include "hostsock.hpp"

namespace {

// ---- wire tags (codec.py WIRE_TAG) ----------------------------------------
enum WireTag : uint16_t {
  T_FA_PUT = 1001,
  T_FA_PUT_COMMON = 1003,
  T_FA_BATCH_DONE = 1005,
  T_FA_DID_PUT_AT_REMOTE = 1006,
  T_FA_RESERVE = 1007,
  T_TA_RESERVE_RESP = 1008,
  T_FA_GET_RESERVED = 1009,
  T_TA_GET_RESERVED_RESP = 1010,
  T_FA_NO_MORE_WORK = 1011,
  T_FA_LOCAL_APP_DONE = 1012,
  T_TA_PUT_RESP = 1020,
  T_FA_ABORT = 1027,
  T_FA_INFO_NUM_WORK_UNITS = 1037,
  T_FA_GET_COMMON = 1038,
  T_TA_GET_COMMON_RESP = 1039,
  T_FA_INFO_GET = 1041,
  T_TA_PUT_COMMON_RESP = 1042,
  T_TA_INFO_NUM_RESP = 1043,
  T_TA_INFO_GET_RESP = 1044,
  T_TA_ABORT = 1046,
  T_FA_CHECKPOINT = 1048,
  T_TA_CHECKPOINT_RESP = 1049,
  T_AM_APP = 1047,
};

// ---- field ids (codec.py FIELDS) ------------------------------------------
enum Field : uint8_t {
  F_PAYLOAD = 1,
  F_WORK_TYPE = 2,
  F_PRIO = 3,
  F_TARGET_RANK = 4,
  F_ANSWER_RANK = 5,
  F_COMMON_LEN = 6,
  F_COMMON_SERVER = 7,
  F_COMMON_SEQNO = 8,
  F_RC = 9,
  F_HINT = 10,
  F_REQ_TYPES = 11,
  F_HANG = 12,
  F_RQSEQNO = 13,
  F_HANDLE = 14,
  F_WORK_LEN = 15,
  F_TIME_ON_Q = 16,
  F_COUNT = 17,
  F_NBYTES = 18,
  F_MAX_WQ = 19,
  F_CODE = 20,
  F_SEQNO = 21,
  F_REFCNT = 22,
  F_SERVER_RANK = 23,
  F_KEY = 24,
  F_VALUE = 25,
  F_APPTAG = 26,
  F_PUT_ID = 58,
  F_FETCH = 59,
  F_FETCH_MAX = 79,
  F_PAYLOADS = 80,
  F_WORK_TYPES = 81,
  F_PRIOS = 82,
  F_ANSWER_RANKS = 83,
  F_PATH = 72,
  F_RETRY_AFTER_MS = 93,
};

enum Kind : uint8_t {
  K_I64 = 0, K_BYTES = 1, K_LIST = 2, K_F64 = 3,
  K_BLIST = 4,  // list of byte strings: u16 count, (u32 len + bytes)*
  K_FLIST = 5,  // list of f64: u16 count, f64*
};

constexpr uint8_t BINARY_MAGIC = 0x01;

struct Msg {
  uint16_t tag = 0;
  int32_t src = -1;
  std::map<uint8_t, int64_t> ints;
  std::map<uint8_t, double> dbls;
  std::map<uint8_t, std::string> blobs;
  std::map<uint8_t, std::vector<int64_t>> lists;
  std::map<uint8_t, std::vector<std::string>> blists;
  std::map<uint8_t, std::vector<double>> flists;

  int64_t geti(uint8_t f, int64_t dflt = 0) const {
    auto it = ints.find(f);
    return it == ints.end() ? dflt : it->second;
  }
};

// ---- encoding -------------------------------------------------------------

void put_u16(std::string &b, uint16_t v) { b.append((const char *)&v, 2); }
void put_u32(std::string &b, uint32_t v) { b.append((const char *)&v, 4); }
void put_i32(std::string &b, int32_t v) { b.append((const char *)&v, 4); }
void put_i64(std::string &b, int64_t v) { b.append((const char *)&v, 8); }
void put_f64(std::string &b, double v) { b.append((const char *)&v, 8); }

struct Encoder {
  std::string body;
  uint16_t nfields = 0;

  explicit Encoder(uint16_t tag, int32_t src) {
    put_u32(body, 0);  // the frame's length prefix, backpatched in finish()
    body.push_back((char)BINARY_MAGIC);
    put_u16(body, tag);
    put_i32(body, src);
    put_u16(body, 0);  // nfields backpatched in finish()
  }
  Encoder &i(uint8_t f, int64_t v) {
    body.push_back((char)f);
    body.push_back((char)K_I64);
    put_i64(body, v);
    nfields++;
    return *this;
  }
  Encoder &bytes(uint8_t f, const void *p, size_t n) {
    body.push_back((char)f);
    body.push_back((char)K_BYTES);
    put_u32(body, (uint32_t)n);
    body.append((const char *)p, n);
    nfields++;
    return *this;
  }
  Encoder &list(uint8_t f, const std::vector<int64_t> &v) {
    body.push_back((char)f);
    body.push_back((char)K_LIST);
    put_u16(body, (uint16_t)v.size());
    for (int64_t x : v) put_i64(body, x);
    nfields++;
    return *this;
  }
  // The whole frame, length prefix and all: one send, one packet, one
  // wake-up of the peer's reading thread.
  std::string finish() {
    uint32_t len = (uint32_t)(body.size() - 4);
    memcpy(&body[0], &len, 4);
    memcpy(&body[11], &nfields, 2);  // offset of nfields in the header
    return std::move(body);
  }
};

bool decode(std::string_view body, Msg *out) {
  if (body.size() < 9 || (uint8_t)body[0] != BINARY_MAGIC) return false;
  size_t off = 1;
  auto need = [&](size_t n) { return off + n <= body.size(); };
  auto rd = [&](void *p, size_t n) {
    memcpy(p, body.data() + off, n);
    off += n;
  };
  uint16_t nf;
  rd(&out->tag, 2);
  rd(&out->src, 4);
  rd(&nf, 2);
  for (uint16_t k = 0; k < nf; k++) {
    if (!need(2)) return false;
    uint8_t fid = body[off], kind = body[off + 1];
    off += 2;
    if (kind == K_I64) {
      if (!need(8)) return false;
      int64_t v;
      rd(&v, 8);
      out->ints[fid] = v;
    } else if (kind == K_BYTES) {
      if (!need(4)) return false;
      uint32_t n;
      rd(&n, 4);
      if (!need(n)) return false;
      out->blobs[fid].assign(body.data() + off, n);
      off += n;
    } else if (kind == K_LIST) {
      if (!need(2)) return false;
      uint16_t cnt;
      rd(&cnt, 2);
      if (!need((size_t)8 * cnt)) return false;
      auto &lst = out->lists[fid];
      lst.resize(cnt);
      for (uint16_t j = 0; j < cnt; j++) rd(&lst[j], 8);
    } else if (kind == K_F64) {
      if (!need(8)) return false;
      double v;
      rd(&v, 8);
      out->dbls[fid] = v;
    } else if (kind == K_BLIST) {
      if (!need(2)) return false;
      uint16_t cnt;
      rd(&cnt, 2);
      auto &bl = out->blists[fid];
      bl.reserve(cnt);
      for (uint16_t j = 0; j < cnt; j++) {
        if (!need(4)) return false;
        uint32_t n;
        rd(&n, 4);
        if (!need(n)) return false;
        bl.emplace_back(body.data() + off, n);
        off += n;
      }
    } else if (kind == K_FLIST) {
      if (!need(2)) return false;
      uint16_t cnt;
      rd(&cnt, 2);
      if (!need((size_t)8 * cnt)) return false;
      auto &fl = out->flists[fid];
      fl.resize(cnt);
      for (uint16_t j = 0; j < cnt; j++) rd(&fl[j], 8);
    } else {
      return false;
    }
  }
  // exact-frame check: every legitimate encoder emits no trailing bytes,
  // so leftovers mean garbage that decoded by luck
  if (off != body.size()) return false;
  // client-bound wire tags live in the 1001-1049 block; anything else is
  // crafted or version-skewed and must not reach the dispatch paths,
  // whose unexpected-tag arms are fatal
  if (out->tag < 1001 || out->tag > 1049) return false;
  return true;
}

// ---- context --------------------------------------------------------------

struct InConn {
  int fd = -1;
  std::string buf;  // bytes received and not yet decoded: at most one
                    // partial frame once parse_frames has run
  bool established = false;  // has delivered a decodable frame
  // a Unix connection begins with the connector's hello, which may bring a
  // ring: then the frames come through it and the socket carries bells
  bool hello_due = false;
  hostsock::HelloRx hello;
  hostsock::RingRx ring;
};

// A connection this rank opened: the socket, and the ring it made for it
// when the peer is a native rank of this host.
struct OutConn {
  int fd = -1;
  hostsock::RingTx ring;
};

struct Ctx {
  int rank = -1, nranks = 0, nservers = 0, num_app_ranks = 0, home = -1;
  int aprintf_flag = 0;
  std::vector<int> types;
  std::vector<std::pair<std::string, int>> addr;  // per rank

  int listen_fd = -1;       // TCP, at the port the rendezvous file gives
  int listen_unix_fd = -1;  // the same port's name (hostsock.hpp), or -1
  // connections opened and accepted, by family (ADLB_TRACE reports them)
  int conns_unix = 0, conns_tcp = 0;
  // waits for an answer that ended inside the polling phase, and waits that
  // went on to sleep in poll() (wait_for; ADLB_TRACE reports them)
  int64_t waits_polled = 0, waits_slept = 0;
  // the connection that delivered the last frame, whether the one before
  // came over it too, and whether it came through a ring: a rank whose
  // answers keep coming over one connection (its home server's, as a rule)
  // looks at that one first
  int last_fd = -1;
  bool last_fd_twice = false;
  bool last_ring = false;
  std::vector<InConn> in;   // inbound connections, read by whoever waits
  std::deque<Msg> inbox;    // decoded frames no call has looked at yet
  std::deque<Msg> app_inbox;  // stashed AM_APP frames (the app_comm channel)
  std::map<int, OutConn> out;

  int rr = 0;       // round-robin cursor over servers
  bool route_home = false;  // ADLB_PUT_ROUTING=home: untargeted puts -> home
  int rqseqno = 0;  // reserve sequence number
  // batch-put state (reference src/adlb.c:2638-2751)
  bool batch_active = false;
  int batch_server = -1, batch_len = 0, batch_refcnt = 0;
  int64_t batch_seqno = -1;
};

Ctx *g = nullptr;

double monotonic() {
  timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return double(ts.tv_sec) + double(ts.tv_nsec) * 1e-9;
}

void die(const char *fmt, ...) {
  va_list ap;
  va_start(ap, fmt);
  fprintf(stderr, "[adlb rank %d] ", g ? g->rank : -1);
  vfprintf(stderr, fmt, ap);
  fprintf(stderr, "\n");
  va_end(ap);
  exit(1);
}

// ---- sockets --------------------------------------------------------------

// Decode every complete frame of c.buf into g->inbox, keeping a partial
// tail. Returns false when the connection must close.
//
// Robustness policy (mirrors serverd.cpp): a connection that has never
// delivered a decodable frame is untrusted — garbage on it closes the
// connection without touching the world (a stray scanner must not kill
// a rank, and rank death kills the whole world). Once a frame has
// decoded, the peer is a real rank: corruption on an ESTABLISHED
// stream is a protocol error and fails fast — dropping it instead
// could discard the response a blocking caller is parked on, turning
// a diagnosable failure into a silent distributed hang.
bool parse_frames(InConn &c) {
  static const uint32_t kMaxFrame = 1u << 28;  // 256 MB
  size_t off = 0;
  bool keep = true;
  while (c.buf.size() - off >= 4) {
    uint32_t len;
    memcpy(&len, c.buf.data() + off, 4);
    if (len > kMaxFrame) {
      // cap before a byte of the body is buffered: a hostile 4 GB prefix
      // must not become the allocation that kills this rank
      if (c.established)
        die("frame length %u exceeds %u cap on an established connection",
            len, kMaxFrame);
      std::fprintf(stderr,
                   "[libadlb] frame length %u exceeds %u cap; closing "
                   "connection\n", len, kMaxFrame);
      keep = false;
      break;
    }
    if (c.buf.size() - off - 4 < len) break;  // the rest has not arrived
    std::string_view body(c.buf.data() + off + 4, len);
    off += 4 + (size_t)len;
    Msg m;
    if (len == 0 || (uint8_t)body[0] != BINARY_MAGIC) {
      if (len > 0 && (uint8_t)body[0] == 0x80 &&
          body.find("adlb_tpu") != std::string_view::npos) {
        // pickle protocol-2+ magic AND the pickled Msg's embedded module
        // path: a Python server that has not yet learned this rank is a
        // binary peer pickles its frames, and the only unsolicited
        // pickled client-bound message is the TA_ABORT fan-out — honor
        // it. (The module-path check keeps 0x80-prefixed line noise from
        // synthesizing a fatal abort; test_codec.py pins the invariant.)
        m.tag = T_TA_ABORT;
        m.ints[F_CODE] = ADLB_ERROR;
      } else if (!c.established) {
        std::fprintf(stderr,
                     "[libadlb] closing connection after non-binary "
                     "frame (%u B)\n", len);
        keep = false;
        break;
      } else {
        die("non-binary frame (%u bytes) on an established connection",
            len);
      }
    } else if (!decode(body, &m)) {
      if (!c.established) {
        std::fprintf(stderr,
                     "[libadlb] closing connection after undecodable "
                     "first frame (%u B) — stray connection, or a "
                     "version-skewed peer (if a caller now hangs, "
                     "rebuild both sides from one tree)\n", len);
        keep = false;
        break;
      }
      die("undecodable binary frame (%u bytes) from a live peer", len);
    } else {
      c.established = true;
    }
    g->inbox.push_back(std::move(m));
    ++(c.ring.on() ? hostsock::ring_stats().frames_ring
                   : hostsock::ring_stats().frames_sock);
  }
  c.buf.erase(0, off);
  return keep;
}

// Parse what a read brought and remember which connection delivered.
bool parse_read(InConn &c) {
  size_t had = g->inbox.size();
  bool keep = parse_frames(c);
  if (g->inbox.size() > had) {
    g->last_fd_twice = g->last_fd == c.fd;
    g->last_fd = c.fd;
    g->last_ring = c.ring.on();
  }
  return keep;
}

// Take what c's ring holds, give the room back (with a bell if the writer
// waits for it) and parse. Memory only, but for that bell. False: garbage.
bool take_ring(InConn &c) {
  bool bell;
  ssize_t n = c.ring.take(c.buf, &bell);
  if (n < 0) {
    if (c.established) die("ring cursors corrupt on an established connection");
    return false;
  }
  if (n == 0) return true;
  if (bell) hostsock::ring_bell(c.fd);  // a lost peer shows as EOF by itself
  return parse_read(c);
}

// One read of what has arrived on c, never blocking, then parse. The buffer
// grows with the bytes actually received, never with the advertised length:
// a connection that sends a large length prefix and then stalls pins
// neither that memory nor the calling thread. On a connection with a ring
// the socket's bytes are bells and the frames are taken from the ring; at
// EOF what the ring still holds comes first. False at EOF, error or
// garbage (a Unix connection that does not begin with the hello is
// garbage): the caller closes the connection.
bool read_conn(InConn &c) {
  if (c.hello_due) {
    switch (hostsock::recv_hello(c.fd, c.hello, &c.ring)) {
      case hostsock::Hello::kMore: return true;
      case hostsock::Hello::kBad: return false;
      case hostsock::Hello::kRing: c.ring.sleeps(false); break;
      case hostsock::Hello::kSocket: break;
    }
    c.hello_due = false;
  }
  char chunk[65536];
  ssize_t r = recv(c.fd, chunk, sizeof chunk, MSG_DONTWAIT);
  bool open = r > 0 || (r < 0 && (errno == EAGAIN || errno == EWOULDBLOCK ||
                                  errno == EINTR));
  if (c.ring.on()) return take_ring(c) && open;
  if (r <= 0) return open;
  c.buf.append(chunk, (size_t)r);
  return parse_read(c);
}

template <class Conn>  // InConn or OutConn: the socket and the mapping
void close_conn(Conn &c) {
  close(c.fd);
  c.ring.close();
  c.fd = -1;
}

void drop_closed() {
  g->in.erase(std::remove_if(g->in.begin(), g->in.end(),
                             [](const InConn &c) { return c.fd < 0; }),
              g->in.end());
}

// A look at every inbound ring: memory reads, one line a ring. What the
// rings hold goes to the inbox.
void scan_rings() {
  bool closed = false;
  for (InConn &c : g->in)
    if (c.ring.on() && c.ring.ready() && !take_ring(c)) {
      close_conn(c);
      closed = true;
    }
  if (closed) drop_closed();
}

// Mark this rank asleep (or awake again) in every inbound ring, so that a
// writer rings the socket's bell. True: some ring holds bytes after the
// mark and the fence, so there is nothing to sleep for.
bool mark_asleep(bool on) {
  bool any = false;
  for (InConn &c : g->in)
    if (c.ring.on()) {
      c.ring.sleeps(on);
      any = true;
    }
  if (!on || !any) return false;
  hostsock::sleep_fence();
  for (InConn &c : g->in)
    if (c.ring.on() && c.ring.ready()) return true;
  return false;
}

// The library's one look at its connections: the inbound rings first (memory),
// then poll() over the two listeners, every inbound connection and
// (optionally) one outbound socket a send is stuck on (`wev`: POLLOUT for a
// full socket, POLLIN for the bell of a full ring); accepts, reads and
// decodes whatever is ready into g->inbox. With `block` it sleeps there and
// returns once `wfd` is ready or, given none, once the inbox holds a frame
// (at once, and without the system call, if the rings held one); without,
// it takes what is there now and returns (the polling phase of wait_for is
// this form, repeated, while answers come over a socket). Before it sleeps
// it marks this rank asleep in its rings, fences and looks at them once
// more; nothing insures that sleep but the handshake (hostsock.hpp).
void poll_inbound(bool block, int wfd = -1, short wev = POLLOUT) {
  static std::vector<struct pollfd> pfds;
  for (;;) {
    scan_rings();
    if (block && wfd < 0 && !g->inbox.empty()) return;
    bool marked = block && !mark_asleep(true);
    pfds.clear();
    // a listener that is not there is -1, which poll() passes over
    pfds.push_back({g->listen_fd, POLLIN, 0});
    pfds.push_back({g->listen_unix_fd, POLLIN, 0});
    for (const InConn &c : g->in) pfds.push_back({c.fd, POLLIN, 0});
    if (wfd >= 0) pfds.push_back({wfd, wev, 0});
    int n = poll(pfds.data(), pfds.size(), marked ? -1 : 0);
    if (n < 0 && errno != EINTR) die("poll: %s", strerror(errno));
    if (block) mark_asleep(false);
    if (n > 0) {
      size_t nconn = g->in.size();
      for (size_t i = 0; i < nconn; i++) {
        InConn &c = g->in[i];
        if (pfds[2 + i].revents != 0 && !read_conn(c)) close_conn(c);
      }
      drop_closed();
      for (int l = 0; l < 2; l++) {
        if (pfds[l].revents == 0) continue;
        for (;;) {  // the listeners are non-blocking: take all that wait
          int fd = accept(pfds[l].fd, nullptr, nullptr);
          if (fd < 0) break;
          g->in.emplace_back();
          g->in.back().fd = fd;
          g->in.back().hello_due = l == 1;
          ++(l == 0 ? g->conns_tcp : g->conns_unix);
        }
      }
      if (wfd >= 0 && pfds.back().revents != 0) return;
    }
    if (!block || (wfd < 0 && !g->inbox.empty())) return;
    // woke for a connection, a partial frame, a ring or a signal: again
  }
}

// A send that would block never stops this rank's reads: while the socket
// or the ring is full the thread waits in poll_inbound, so two ranks sending
// each other more than their buffers hold both get through. Through a ring
// the frame goes in as many installments as its size asks for, each
// published (and the reader woken, if it sleeps) as it is written.
bool write_all(OutConn &oc, const void *p, size_t n) {
  const char *c = (const char *)p;
  while (n > 0 && oc.ring.on()) {
    size_t w = oc.ring.write(c, n);
    c += w;
    n -= w;
    if (w > 0) {
      if (!oc.ring.kick(oc.fd)) return false;
      continue;
    }
    if (!oc.ring.wait_room()) continue;
    poll_inbound(true, oc.fd, POLLIN);  // the reader's bell, or its death
    if (!hostsock::drain_bells(oc.fd)) return false;
  }
  while (n > 0) {
    ssize_t r = send(oc.fd, c, n, MSG_DONTWAIT | MSG_NOSIGNAL);
    if (r < 0 && (errno == EAGAIN || errno == EWOULDBLOCK)) {
      poll_inbound(true, oc.fd);
      continue;
    }
    if (r < 0 && errno == EINTR) continue;
    if (r <= 0) return false;
    c += r;
    n -= (size_t)r;
  }
  return true;
}

// The family comes from the address map and the peer's answer alone
// (hostsock.hpp): a destination on this rank's host is tried at its port's
// Unix name first, on every attempt, so a peer that is not up yet (it
// refuses both) never pins the pair on TCP; a peer with no such listener (a
// Python rank) and a destination on another host get TCP. A Unix connection
// begins with the hello, and with it the ring when one can be made.
OutConn connect_to(int dest) {
  auto &hp = g->addr[dest];
  bool local = hostsock::same_host(hp.first, g->addr[g->rank].first);
  struct addrinfo hints = {}, *res = nullptr;
  hints.ai_family = AF_INET;
  hints.ai_socktype = SOCK_STREAM;
  char port[16];
  snprintf(port, sizeof port, "%d", hp.second);
  // servers may come up after us: retry with backoff for ~15 s
  for (int attempt = 0; attempt < 60; attempt++) {
    OutConn oc;
    oc.fd = local ? hostsock::connect_unix(hp.second) : -1;
    if (oc.fd >= 0) {
      if (oc.ring.open(oc.fd)) {
        g->conns_unix++;
        return oc;
      }
      close(oc.fd);  // gone between connect and hello: try again
      oc.fd = -1;
    }
    if (getaddrinfo(hp.first.c_str(), port, &hints, &res) == 0) {
      int fd = socket(res->ai_family, res->ai_socktype, res->ai_protocol);
      if (fd >= 0 && connect(fd, res->ai_addr, res->ai_addrlen) == 0) {
        int one = 1;
        setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof one);
        freeaddrinfo(res);
        g->conns_tcp++;
        oc.fd = fd;
        return oc;
      }
      if (fd >= 0) close(fd);
      freeaddrinfo(res);
      res = nullptr;
    }
    usleep(250 * 1000);
  }
  die("cannot connect to rank %d at %s:%d", dest, hp.first.c_str(), hp.second);
  return OutConn();
}

void send_msg(int dest, Encoder &enc) {
  std::string frame = enc.finish();
  OutConn &oc = g->out[dest];
  if (oc.fd < 0) oc = connect_to(dest);
  if (!write_all(oc, frame.data(), frame.size())) {
    close_conn(oc);
    oc = connect_to(dest);  // one reconnect attempt
    if (!write_all(oc, frame.data(), frame.size()))
      die("send to rank %d failed", dest);
  }
}

// ---- pipelined puts (iput; no reference analogue — upstream's Put is one
// synchronous round trip per unit, src/adlb.c:2811-2843). Requests carry a
// put_id echoed in the response; settle out of band, replaying rejects at
// the hinted server with the synchronous path's pacing. ------------------
int home_server(int app_rank);
int next_server();

struct PendingPut {
  std::string payload;
  int work_type, prio, target_rank, answer_rank, attempts, server;
  int backoff_ms = 0;  // ADLB_BACKOFF retry-after hint awaiting replay
};
static std::map<int64_t, PendingPut> pending_puts;
static std::vector<int64_t> resend_queue;  // rejected ids awaiting replay
static int64_t next_put_id = 1;
static int failed_puts = 0;
static bool failed_nmw = false;

static void send_iput(int64_t id, const PendingPut &pp) {
  Encoder e(T_FA_PUT, g->rank);
  e.bytes(F_PAYLOAD, pp.payload.data(), pp.payload.size())
      .i(F_WORK_TYPE, pp.work_type)
      .i(F_PRIO, pp.prio)
      .i(F_TARGET_RANK, pp.target_rank)
      .i(F_ANSWER_RANK, pp.answer_rank)
      .i(F_COMMON_LEN, 0)
      .i(F_COMMON_SERVER, -1)
      .i(F_COMMON_SEQNO, -1)
      .i(F_PUT_ID, id);
  send_msg(pp.server, e);
}

static void settle_put(const Msg &m) {
  int64_t id = m.geti(F_PUT_ID);
  auto it = pending_puts.find(id);
  if (it == pending_puts.end()) return;
  int rc = (int)m.geti(F_RC);
  if (rc == ADLB_BACKOFF) {
    // backpressured pipelined put: replay toward the same server without
    // burning the reject budget, pacing by the server's carried hint
    // (pump_resends sleeps it — the fixed 2 ms resend pace would hammer
    // the saturated server ~12x faster than it asked for, defeating the
    // load shedding)
    it->second.backoff_ms = (int)m.geti(F_RETRY_AFTER_MS, 25);
    resend_queue.push_back(id);
    return;
  }
  if (rc == ADLB_PUT_REJECTED && ++it->second.attempts <= 10) {
    int hint = (int)m.geti(F_HINT, -1);
    it->second.server = hint >= 0 ? hint : next_server();
    // replay happens in pump_resends(), once the frames already read have
    // all been looked at: sleeping or sending here would hold up the
    // responses (and an abort) queued behind this one
    resend_queue.push_back(id);
    return;
  }
  if (rc != ADLB_SUCCESS) {
    failed_puts++;
    if (rc == ADLB_NO_MORE_WORK) failed_nmw = true;
  } else if (it->second.target_rank >= 0 &&
             it->second.server != home_server(it->second.target_rank)) {
    Encoder e(T_FA_DID_PUT_AT_REMOTE, g->rank);
    e.i(F_TARGET_RANK, it->second.target_rank)
        .i(F_WORK_TYPE, it->second.work_type)
        .i(F_SERVER_RANK, it->second.server);
    send_msg(home_server(it->second.target_rank), e);
  }
  pending_puts.erase(it);
}

// Replay rejected pipelined puts queued by settle_put. Called between
// drains of the inbox, never from inside one: the pacing sleep and the
// (possibly connect-blocking) send come after every frame already read
// has been handled.
static void pump_resends() {
  while (!resend_queue.empty()) {
    int64_t id = resend_queue.front();
    resend_queue.erase(resend_queue.begin());
    auto it = pending_puts.find(id);
    if (it == pending_puts.end()) continue;
    // a backpressured put sleeps the server's retry-after hint; a
    // rejected-and-rerouted one paces like the synchronous retry loop
    usleep(it->second.backoff_ms > 0
               ? (useconds_t)it->second.backoff_ms * 1000
               : 2000);
    it->second.backoff_ms = 0;  // hint consumed by this replay
    send_iput(id, it->second);
  }
}

// Handle a frame that is not an awaited protocol response: abort frames
// terminate (the reference client dies inside MPI_Abort in the same
// situation, reference src/adlb.c:3165-3176), app_comm traffic is stashed,
// anything else is fatal.
void dispatch_passive(Msg m) {
  if (m.tag == T_TA_ABORT) {
    int code = (int)m.geti(F_CODE, ADLB_ERROR);
    fprintf(stderr, "[adlb rank %d] world aborted (code %d)\n", g->rank,
            code);
    exit(code == 0 ? 1 : (code < 0 ? -code : code));
  }
  if (m.tag == T_AM_APP) {
    g->app_inbox.push_back(std::move(m));
    return;
  }
  if (m.tag == T_TA_PUT_RESP && m.ints.count(F_PUT_ID)) {
    settle_put(m);
    return;
  }
  die("unexpected tag %u outside a pending request", m.tag);
}

// Until the inbox holds a frame: look for it without blocking for the
// polling budget, then sleep. The caller has a request out, so the frame is
// on its way. While the answers come through a ring a look is a read of the
// inbound rings' tails (a handful of cache lines at a client, so no ring is
// looked at first): no system call, and the descriptors (a frame from a TCP
// peer, a new connection) wait for the sleep that ends the phase, one
// budget at most. While they come over a socket a look is poll() over the
// same descriptors as the sleep, and where the last two frames came over
// one connection it starts with a read of that connection alone: a hit
// there costs one system call, not poll() and then the read; the others are
// still looked at in the same turn.
void await_answer() {
  double budget = hostsock::poll_budget_s();
  if (budget > 0) {
    double deadline = monotonic() + budget;
    do {
      if (g->last_ring) {
        scan_rings();
      } else {
        auto last = g->last_fd_twice
                        ? std::find_if(g->in.begin(), g->in.end(),
                                       [](const InConn &c) {
                                         return c.fd == g->last_fd;
                                       })
                        : g->in.end();
        if (last != g->in.end() && !read_conn(*last)) {
          close_conn(*last);
          g->in.erase(last);
        }
        if (g->inbox.empty()) poll_inbound(false);
      }
      if (!g->inbox.empty()) {
        g->waits_polled++;
        return;
      }
    } while (monotonic() < deadline);
  }
  g->waits_slept++;
  poll_inbound(true);
}

// Blocks until a frame with `want` arrives, reading the sockets itself: the
// thread that waits is the thread that reads, so a response costs this side
// no thread hand-off, and no wake-up either when it comes within the polling
// budget. Frames read on the way that are not the awaited one are handled
// here, on the caller's thread.
Msg wait_for(uint16_t want) {
  for (;;) {
    while (!g->inbox.empty()) {
      Msg m = std::move(g->inbox.front());
      g->inbox.pop_front();
      if (m.tag == want &&
          !(m.tag == T_TA_PUT_RESP && m.ints.count(F_PUT_ID)))
        return m;
      dispatch_passive(std::move(m));
    }
    pump_resends();  // replays queued by settle_put
    if (g->inbox.empty()) await_answer();
  }
}

int home_server(int app_rank) {
  return g->num_app_ranks + (app_rank % g->nservers);
}

int next_server() {
  // data-locality routing (the Python runtime's put_routing="home"): all
  // of this rank's untargeted puts land on its home server, the scenario
  // shape where cross-server balancing is load-bearing
  if (g->route_home) return g->home;
  int s = g->num_app_ranks + g->rr;
  g->rr = (g->rr + 1) % g->nservers;
  return s;
}

bool valid_type(int t) {
  for (int x : g->types)
    if (x == t) return true;
  return false;
}

}  // namespace

// ---- public API -----------------------------------------------------------

extern "C" {

// ---- run-time tracing: the reference's MPE profiling wrapper layer
// (reference src/adlb_prof.c — compile-time LOG_ADLB_INTERNALS per-call
// state events and LOG_GUESS_USER_STATE inferred per-type user intervals
// between Get_reserved calls), gated here by ADLB_TRACE=<path prefix> at
// run time. ADLB_Finalize writes <prefix>.<rank>.trace.json in Chrome
// trace-event format (one file per rank; concatenate the arrays to merge).
struct TraceEv {
  const char *name;
  int wt;  // work type for inferred user states, -1 for API calls
  double ts, dur;
};
static bool trace_on = false;
static std::string trace_prefix;
static std::vector<TraceEv> trace_events;
static double trace_user_t0 = -1.0;
static int trace_user_wt = -1;
static int trace_last_reserved_wt = -1;

static void trace_api_entry() {
  if (!trace_on) return;
  if (trace_user_t0 >= 0) {  // close the open inferred user-state span
    trace_events.push_back(
        {"user", trace_user_wt, trace_user_t0, monotonic() - trace_user_t0});
    trace_user_t0 = -1.0;
  }
}
static void trace_call(const char *name, double t0) {
  if (!trace_on) return;
  trace_events.push_back({name, -1, t0, monotonic() - t0});
}
static void trace_got_work() {  // successful Get_reserved opens a user span
  if (!trace_on) return;
  trace_user_t0 = monotonic();
  trace_user_wt = trace_last_reserved_wt;
}
static void trace_flush(int rank) {
  if (!trace_on) return;
  trace_api_entry();
  std::string path = trace_prefix + "." + std::to_string(rank) +
                     ".trace.json";
  FILE *f = fopen(path.c_str(), "w");
  if (f == nullptr) return;
  fprintf(f, "[");
  for (size_t i = 0; i < trace_events.size(); ++i) {
    const TraceEv &e = trace_events[i];
    if (i) fprintf(f, ",");
    if (e.wt >= 0)
      fprintf(f,
              "{\"name\":\"user:type%d\",\"ph\":\"X\",\"ts\":%.3f,"
              "\"dur\":%.3f,\"pid\":%d,\"tid\":%d}",
              e.wt, e.ts * 1e6, e.dur * 1e6, rank, rank);
    else
      fprintf(f,
              "{\"name\":\"%s\",\"ph\":\"X\",\"ts\":%.3f,"
              "\"dur\":%.3f,\"pid\":%d,\"tid\":%d}",
              e.name, e.ts * 1e6, e.dur * 1e6, rank, rank);
  }
  // the transport's counter: connections this rank opened and accepted,
  // by socket family (hostsock.hpp)
  fprintf(f,
          "%s{\"name\":\"adlb:conns\",\"ph\":\"C\",\"ts\":%.3f,"
          "\"pid\":%d,\"tid\":%d,\"args\":{\"conns_unix\":%d,"
          "\"conns_tcp\":%d}}",
          trace_events.empty() ? "" : ",", monotonic() * 1e6, rank, rank,
          g ? g->conns_unix : 0, g ? g->conns_tcp : 0);
  // and how its waits for an answer ended: inside the polling phase, or
  // asleep in poll() (wait_for)
  fprintf(f,
          ",{\"name\":\"adlb:waits\",\"ph\":\"C\",\"ts\":%.3f,"
          "\"pid\":%d,\"tid\":%d,\"args\":{\"waits_polled\":%lld,"
          "\"waits_slept\":%lld}}",
          monotonic() * 1e6, rank, rank,
          (long long)(g ? g->waits_polled : 0),
          (long long)(g ? g->waits_slept : 0));
  // and what the rings did (hostsock.hpp): frames received by path, bells
  // sent, publishes that found the reader awake
  const hostsock::RingStats &rs = hostsock::ring_stats();
  fprintf(f,
          ",{\"name\":\"adlb:rings\",\"ph\":\"C\",\"ts\":%.3f,"
          "\"pid\":%d,\"tid\":%d,\"args\":{\"frames_ring\":%lld,"
          "\"frames_sock\":%lld,\"bells_rung\":%lld,"
          "\"bells_elided\":%lld}}",
          monotonic() * 1e6, rank, rank, (long long)rs.frames_ring,
          (long long)rs.frames_sock, (long long)rs.bells_rung,
          (long long)rs.bells_elided);
  fprintf(f, "]\n");
  fclose(f);
}

int ADLBP_Init(int num_servers, int use_debug_server, int aprintf_flag,
               int ntypes, int type_vect[], int *am_server,
               int *am_debug_server, int *num_app_ranks) {
  if (g) return ADLB_ERROR;
  if (num_servers <= 0) {
    // without this, home_server()'s rank % num_servers dies with an
    // unexplained SIGFPE (the reference asserts the same way,
    // src/adlb.c:238)
    fprintf(stderr, "adlb: num_servers must be positive (got %d)\n",
            num_servers);
    return ADLB_ERROR;
  }
  const char *rv = getenv("ADLB_RENDEZVOUS");
  const char *rk = getenv("ADLB_RANK");
  if (!rv || !rk) {
    fprintf(stderr, "adlb: ADLB_RENDEZVOUS and ADLB_RANK must be set\n");
    return ADLB_ERROR;
  }
  g = new Ctx();
  g->rank = atoi(rk);
  g->aprintf_flag = aprintf_flag;
  g->types.assign(type_vect, type_vect + ntypes);

  FILE *f = fopen(rv, "r");
  if (!f) die("cannot open rendezvous file %s", rv);
  int r, port;
  char host[256];
  int maxrank = -1;
  std::map<int, std::pair<std::string, int>> entries;
  while (fscanf(f, "%d %255s %d", &r, host, &port) == 3) {
    entries[r] = {host, port};
    if (r > maxrank) maxrank = r;
  }
  fclose(f);
  g->nranks = maxrank + 1;
  g->addr.resize(g->nranks);
  for (auto &kv : entries) g->addr[kv.first] = kv.second;
  g->nservers = num_servers;
  g->num_app_ranks = g->nranks - num_servers - (use_debug_server ? 1 : 0);
  if (g->rank < 0 || g->rank >= g->num_app_ranks)
    die("ADLB_RANK %d is not an app rank (0..%d)", g->rank,
        g->num_app_ranks - 1);
  g->home = home_server(g->rank);
  g->rr = g->rank % g->nservers;
  const char *routing = getenv("ADLB_PUT_ROUTING");
  g->route_home = (routing != nullptr && strcmp(routing, "home") == 0);

  // the port's Unix name first (hostsock.hpp): whoever finds the TCP port
  // open below has had the name to try
  g->listen_unix_fd = hostsock::listen_unix(g->addr[g->rank].second, 1024);
  if (g->listen_unix_fd < 0 && errno == EADDRINUSE)
    die("cannot bind port %d: its Unix name is taken",
        g->addr[g->rank].second);
  // bind our listener at the advertised address
  g->listen_fd = socket(AF_INET, SOCK_STREAM | SOCK_NONBLOCK, 0);
  int one = 1;
  setsockopt(g->listen_fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof one);
  struct sockaddr_in sa = {};
  sa.sin_family = AF_INET;
  sa.sin_addr.s_addr = htonl(INADDR_ANY);
  sa.sin_port = htons((uint16_t)g->addr[g->rank].second);
  if (bind(g->listen_fd, (struct sockaddr *)&sa, sizeof sa) != 0)
    die("cannot bind port %d", g->addr[g->rank].second);
  // connections wait here until this rank's next call into the library
  // accepts them (poll_inbound): room for every rank of a large world
  if (listen(g->listen_fd, 1024) != 0) die("listen failed");

  if (am_server) *am_server = 0;
  if (am_debug_server) *am_debug_server = 0;
  if (num_app_ranks) *num_app_ranks = g->num_app_ranks;
  return ADLB_SUCCESS;
}

int ADLB_Init(int num_servers, int use_debug_server, int aprintf_flag,
              int ntypes, int type_vect[], int *am_server,
              int *am_debug_server, int *num_app_ranks) {
  int rc = ADLBP_Init(num_servers, use_debug_server, aprintf_flag, ntypes,
                      type_vect, am_server, am_debug_server, num_app_ranks);
  const char *tp = getenv("ADLB_TRACE");
  if (rc == ADLB_SUCCESS && tp != nullptr && tp[0] != '\0') {
    trace_on = true;
    trace_prefix = tp;
  }
  return rc;
}

int ADLBP_Server(double, double) { return ADLB_ERROR; }
int ADLB_Server(double a, double b) { return ADLBP_Server(a, b); }
int ADLBP_Debug_server(double) { return ADLB_ERROR; }
int ADLB_Debug_server(double t) { return ADLBP_Debug_server(t); }


int ADLBP_Put(void *work_buf, int work_len, int target_rank, int answer_rank,
              int work_type, int work_prio) {
  if (!g) return ADLB_ERROR;
  if (!valid_type(work_type)) die("Put of unregistered type %d", work_type);
  if (g->batch_active) g->batch_refcnt++;
  int server;
  if (target_rank >= 0)
    server = home_server(target_rank);
  else
    server = next_server();
  int attempts = 0;
  int rc;
  for (;;) {
    Encoder e(T_FA_PUT, g->rank);
    e.bytes(F_PAYLOAD, work_buf, (size_t)work_len)
        .i(F_WORK_TYPE, work_type)
        .i(F_PRIO, work_prio)
        .i(F_TARGET_RANK, target_rank)
        .i(F_ANSWER_RANK, answer_rank)
        .i(F_COMMON_LEN, g->batch_active ? g->batch_len : 0)
        .i(F_COMMON_SERVER, g->batch_active ? g->batch_server : -1)
        .i(F_COMMON_SEQNO, g->batch_active ? g->batch_seqno : -1);
    send_msg(server, e);
    Msg resp = wait_for(T_TA_PUT_RESP);
    rc = (int)resp.geti(F_RC);
    if (rc == ADLB_BACKOFF) {
      // overload backpressure: the fleet is above its hard watermark, so
      // hopping servers would not help — wait out the carried hint and
      // retry the SAME server without burning the reject budget
      usleep((useconds_t)resp.geti(F_RETRY_AFTER_MS, 25) * 1000);
      continue;
    }
    if (rc != ADLB_PUT_REJECTED) break;
    if (++attempts > 10) {  // reference retry loop, src/adlb.c:2779-2796
      if (g->batch_active) g->batch_refcnt--;
      return ADLB_PUT_REJECTED;
    }
    int hint = (int)resp.geti(F_HINT, -1);
    server = hint >= 0 ? hint : next_server();
    usleep(2000);
  }
  if (rc != ADLB_SUCCESS && g->batch_active) g->batch_refcnt--;
  if (rc == ADLB_SUCCESS && target_rank >= 0 &&
      server != home_server(target_rank)) {
    Encoder e(T_FA_DID_PUT_AT_REMOTE, g->rank);
    e.i(F_TARGET_RANK, target_rank)
        .i(F_WORK_TYPE, work_type)
        .i(F_SERVER_RANK, server);
    send_msg(home_server(target_rank), e);
  }
  return rc;
}
int ADLB_Put(void *b, int l, int t, int a, int w, int p) {
  if (!trace_on) return ADLBP_Put(b, l, t, a, w, p);
  trace_api_entry();
  double t0 = monotonic();
  int rc = ADLBP_Put(b, l, t, a, w, p);
  trace_call("adlb:put", t0);
  return rc;
}

static int reserve_impl(int *req_types, int *work_type, int *work_prio,
                        int *work_handle, int *work_len, int *answer_rank,
                        int hang, int fetch = 0, Msg *raw = nullptr,
                        int fetch_max = 1) {
  if (!g) return ADLB_ERROR;
  std::vector<int64_t> types;
  bool any = false;
  if (!req_types || req_types[0] == ADLB_RESERVE_REQUEST_ANY) {
    any = true;
  } else {
    for (int i = 0; i < 16 && req_types[i] != ADLB_RESERVE_EOL; i++) {
      if (!valid_type(req_types[i]))
        die("Reserve of unregistered type %d", req_types[i]);
      types.push_back(req_types[i]);
    }
    if (types.empty()) any = true;
  }
  g->rqseqno++;
  Encoder e(T_FA_RESERVE, g->rank);
  e.i(F_HANG, hang).i(F_RQSEQNO, g->rqseqno);
  if (fetch) e.i(F_FETCH, 1);
  if (fetch_max > 1) e.i(F_FETCH_MAX, fetch_max);
  if (!any) e.list(F_REQ_TYPES, types);
  send_msg(g->home, e);
  Msg resp = wait_for(T_TA_RESERVE_RESP);
  int rc = (int)resp.geti(F_RC);
  if (rc != ADLB_SUCCESS) return rc;
  if (work_type) *work_type = (int)resp.geti(F_WORK_TYPE);
  trace_last_reserved_wt = (int)resp.geti(F_WORK_TYPE);
  if (work_prio) *work_prio = (int)resp.geti(F_PRIO);
  if (work_len) *work_len = (int)resp.geti(F_WORK_LEN);
  if (answer_rank) *answer_rank = (int)resp.geti(F_ANSWER_RANK, -1);
  if (raw != nullptr) {  // fused caller inspects payload-vs-handle itself
    *raw = std::move(resp);
    return ADLB_SUCCESS;
  }
  auto it = resp.lists.find(F_HANDLE);
  if (it == resp.lists.end() || it->second.size() != ADLB_HANDLE_SIZE)
    die("malformed reserve handle");
  for (int i = 0; i < ADLB_HANDLE_SIZE; i++)
    work_handle[i] = (int)it->second[i];
  return ADLB_SUCCESS;
}

int ADLBP_Reserve(int *rt, int *wt, int *wp, int *wh, int *wl, int *ar) {
  return reserve_impl(rt, wt, wp, wh, wl, ar, 1);
}
int ADLB_Reserve(int *rt, int *wt, int *wp, int *wh, int *wl, int *ar) {
  if (!trace_on) return reserve_impl(rt, wt, wp, wh, wl, ar, 1);
  trace_api_entry();
  double t0 = monotonic();
  int rc = reserve_impl(rt, wt, wp, wh, wl, ar, 1);
  trace_call("adlb:reserve", t0);
  return rc;
}
int ADLBP_Ireserve(int *rt, int *wt, int *wp, int *wh, int *wl, int *ar) {
  return reserve_impl(rt, wt, wp, wh, wl, ar, 0);
}
int ADLB_Ireserve(int *rt, int *wt, int *wp, int *wh, int *wl, int *ar) {
  if (!trace_on) return reserve_impl(rt, wt, wp, wh, wl, ar, 0);
  trace_api_entry();
  double t0 = monotonic();
  int rc = reserve_impl(rt, wt, wp, wh, wl, ar, 0);
  trace_call("adlb:ireserve", t0);
  return rc;
}

// Fetch a batch-common prefix into *out; advances *out past the prefix.
// Shared by the Get_reserved handle path and the fused suffix+common
// reservation response (the Python server inlines only the SUFFIX of a
// prefixed unit since the remote-fused-fetch change — the client
// assembles prefix + suffix itself). Returns the server's rc: a GC'd
// prefix (reclaim edge) must surface as an error, never as a silently
// truncated payload.
static int fetch_common_prefix(int common_server, int64_t common_seqno,
                               char **out) {
  Encoder e(T_FA_GET_COMMON, g->rank);
  e.i(F_COMMON_SEQNO, common_seqno);
  send_msg(common_server, e);
  Msg resp = wait_for(T_TA_GET_COMMON_RESP);
  int rc = (int)resp.geti(F_RC, ADLB_SUCCESS);
  if (rc != ADLB_SUCCESS) return rc;
  const std::string &prefix = resp.blobs[F_PAYLOAD];
  memcpy(*out, prefix.data(), prefix.size());
  *out += prefix.size();
  return ADLB_SUCCESS;
}

int ADLBP_Get_reserved_timed(void *work_buf, int *work_handle,
                             double *time_on_queue) {
  if (!g) return ADLB_ERROR;
  // handle = {seqno, holder server, common_len, common_server, common_seqno}
  // (reference src/adlb.c:2935-2947)
  int64_t seqno = work_handle[0];
  int holder = work_handle[1];
  int common_len = work_handle[2];
  int common_server = work_handle[3];
  int64_t common_seqno = work_handle[4];
  char *out = (char *)work_buf;
  if (common_len > 0) {
    int rc = fetch_common_prefix(common_server, common_seqno, &out);
    if (rc != ADLB_SUCCESS) return rc;
  }
  Encoder e(T_FA_GET_RESERVED, g->rank);
  e.i(F_SEQNO, seqno);
  send_msg(holder, e);
  Msg resp = wait_for(T_TA_GET_RESERVED_RESP);
  int rc = (int)resp.geti(F_RC);
  // ADLB_FENCED surfaces here as-is: this rank's lease expired while it
  // was silent (lease_timeout_s armed on a Python-server world) and the
  // unit went to another worker — drop the handle and re-reserve
  if (rc != ADLB_SUCCESS) return rc;
  const std::string &payload = resp.blobs[F_PAYLOAD];
  memcpy(out, payload.data(), payload.size());
  if (time_on_queue) {
    auto it = resp.dbls.find(F_TIME_ON_Q);
    *time_on_queue = it == resp.dbls.end() ? 0.0 : it->second;
  }
  return ADLB_SUCCESS;
}
int ADLB_Get_reserved_timed(void *b, int *h, double *t) {
  if (!trace_on) return ADLBP_Get_reserved_timed(b, h, t);
  trace_api_entry();
  double t0 = monotonic();
  int rc = ADLBP_Get_reserved_timed(b, h, t);
  trace_call("adlb:get_reserved", t0);
  if (rc == ADLB_SUCCESS) trace_got_work();
  return rc;
}
int ADLBP_Get_reserved(void *b, int *h) {
  return ADLBP_Get_reserved_timed(b, h, nullptr);
}
int ADLB_Get_reserved(void *b, int *h) {
  return ADLB_Get_reserved_timed(b, h, nullptr);
}

int ADLBP_Begin_batch_put(void *common_buf, int len_common) {
  if (!g || g->batch_active) return ADLB_ERROR;
  int server = next_server();
  Encoder e(T_FA_PUT_COMMON, g->rank);
  e.bytes(F_PAYLOAD, common_buf, (size_t)len_common);
  send_msg(server, e);
  Msg resp = wait_for(T_TA_PUT_COMMON_RESP);
  int rc = (int)resp.geti(F_RC);
  if (rc != ADLB_SUCCESS) return rc;
  g->batch_active = true;
  g->batch_server = server;
  g->batch_len = len_common;
  g->batch_seqno = resp.geti(F_COMMON_SEQNO, -1);
  g->batch_refcnt = 0;
  return ADLB_SUCCESS;
}
int ADLB_Begin_batch_put(void *b, int l) { return ADLBP_Begin_batch_put(b, l); }

int ADLBP_End_batch_put(void) {
  if (!g || !g->batch_active) return ADLB_ERROR;
  Encoder e(T_FA_BATCH_DONE, g->rank);
  e.i(F_COMMON_SEQNO, g->batch_seqno).i(F_REFCNT, g->batch_refcnt);
  send_msg(g->batch_server, e);
  g->batch_active = false;
  return ADLB_SUCCESS;
}
int ADLB_End_batch_put(void) { return ADLBP_End_batch_put(); }

int ADLBP_Set_problem_done(void) {
  if (!g) return ADLB_ERROR;
  Encoder e(T_FA_NO_MORE_WORK, g->rank);
  send_msg(g->home, e);
  return ADLB_SUCCESS;
}
int ADLB_Set_problem_done(void) { return ADLBP_Set_problem_done(); }
int ADLBP_Set_no_more_work(void) { return ADLBP_Set_problem_done(); }
int ADLB_Set_no_more_work(void) { return ADLBP_Set_problem_done(); }

int ADLBP_Info_get(int key, double *value) {
  if (!g) return ADLB_ERROR;
  Encoder e(T_FA_INFO_GET, g->rank);
  e.i(F_KEY, key);
  send_msg(g->home, e);
  Msg resp = wait_for(T_TA_INFO_GET_RESP);
  if (value) {
    auto it = resp.dbls.find(F_VALUE);
    *value = it == resp.dbls.end() ? 0.0 : it->second;
  }
  return (int)resp.geti(F_RC);
}
int ADLB_Info_get(int k, double *v) { return ADLBP_Info_get(k, v); }

int ADLBP_Checkpoint(const char *path_prefix, int *units_captured) {
  // Snapshot the whole pool to <prefix>.<server>.ckpt shards (this
  // framework's extension — the reference has no pool serialization;
  // restore via the daemon's restore_path config). Blocks until every
  // server has written its shard.
  if (!g || path_prefix == nullptr) return ADLB_ERROR;
  Encoder e(T_FA_CHECKPOINT, g->rank);
  e.bytes(F_PATH, path_prefix, strlen(path_prefix));
  send_msg(g->home, e);
  Msg resp = wait_for(T_TA_CHECKPOINT_RESP);
  if (units_captured) *units_captured = (int)resp.geti(F_COUNT);
  return (int)resp.geti(F_RC);
}
int ADLB_Checkpoint(const char *p, int *n) { return ADLBP_Checkpoint(p, n); }

int ADLBP_Info_num_work_units(int work_type, int *num_units, int *num_bytes,
                              int *max_wq_count) {
  if (!g) return ADLB_ERROR;
  Encoder e(T_FA_INFO_NUM_WORK_UNITS, g->rank);
  e.i(F_WORK_TYPE, work_type);
  send_msg(g->home, e);
  Msg resp = wait_for(T_TA_INFO_NUM_RESP);
  if (num_units) *num_units = (int)resp.geti(F_COUNT);
  if (num_bytes) *num_bytes = (int)resp.geti(F_NBYTES);
  if (max_wq_count) *max_wq_count = (int)resp.geti(F_MAX_WQ);
  return (int)resp.geti(F_RC);
}
int ADLB_Info_num_work_units(int w, int *n, int *b, int *m) {
  return ADLBP_Info_num_work_units(w, n, b, m);
}

int ADLBP_Finalize(void) {
  if (!g) return ADLB_ERROR;
  if (!pending_puts.empty()) {
    // un-settled pipelined puts must land before LOCAL_APP_DONE, or the
    // shutdown ring could outrun them
    int rc = ADLBP_Flush_puts();
    if (rc != ADLB_SUCCESS && rc != ADLB_NO_MORE_WORK)
      fprintf(stderr,
              "[adlb rank %d] finalize: pipelined puts terminally "
              "rejected (rc=%d)\n", g->rank, rc);
  }
  Encoder e(T_FA_LOCAL_APP_DONE, g->rank);
  send_msg(g->home, e);
  for (auto &kv : g->out) {
    // FIN after data; no unread inbound. What a ring holds is the reader's
    // to take after the EOF: its mapping outlives this one
    shutdown(kv.second.fd, SHUT_WR);
    close_conn(kv.second);
  }
  g->out.clear();
  for (InConn &c : g->in) close_conn(c);
  g->in.clear();
  close(g->listen_fd);
  g->listen_fd = -1;
  if (g->listen_unix_fd >= 0) close(g->listen_unix_fd);
  g->listen_unix_fd = -1;
  return ADLB_SUCCESS;
}
int ADLB_Finalize(void) {
  trace_api_entry();  // the user state ends here, not after the shutdown
  // the record is written last: LOCAL_APP_DONE may open the last
  // connection it counts
  int rc = ADLBP_Finalize();
  trace_flush(g ? g->rank : -1);
  return rc;
}

int ADLBP_Abort(int code) {
  if (g) {
    Encoder e(T_FA_ABORT, g->rank);
    e.i(F_CODE, code);
    send_msg(g->home, e);
    usleep(100 * 1000);  // let the frame flush before hard exit
  }
  fprintf(stderr, "[adlb rank %d] ADLB_Abort(%d)\n", g ? g->rank : -1, code);
  exit(code == 0 ? 1 : (code < 0 ? -code : code));
}
int ADLB_Abort(int code) { return ADLBP_Abort(code); }

// ---- app <-> app messaging (the reference's app_comm: ADLB_Init returns a
// communicator for direct point-to-point traffic among app ranks, e.g.
// c1.c's TAG_B_ANSWER flow; here the same fabric carries it as AM_APP
// frames with a user tag inside) --------------------------------------------

int ADLBP_App_send(int dest_app_rank, void *buf, int len, int apptag) {
  if (!g) return ADLB_ERROR;
  if (dest_app_rank < 0 || dest_app_rank >= g->num_app_ranks)
    die("App_send: %d is not an app rank", dest_app_rank);
  Encoder e(T_AM_APP, g->rank);
  e.bytes(F_PAYLOAD, buf, (size_t)len).i(F_APPTAG, apptag);
  send_msg(dest_app_rank, e);
  return ADLB_SUCCESS;
}
int ADLB_App_send(int d, void *b, int l, int t) {
  if (!trace_on) return ADLBP_App_send(d, b, l, t);
  trace_api_entry();
  double t0 = monotonic();
  int rc = ADLBP_App_send(d, b, l, t);
  trace_call("adlb:app_send", t0);
  return rc;
}

// Take what the sockets hold now, without waiting, and handle it: outside
// a pending request every frame is a passive one.
static void drain_inbox() {
  poll_inbound(false);
  while (!g->inbox.empty()) {
    Msg m = std::move(g->inbox.front());
    g->inbox.pop_front();
    dispatch_passive(std::move(m));
  }
}

int ADLBP_App_iprobe(int *src, int *apptag, int *len) {
  if (!g) return ADLB_ERROR;
  drain_inbox();
  if (g->app_inbox.empty()) return 0;
  const Msg &m = g->app_inbox.front();
  if (src) *src = m.src;
  if (apptag) *apptag = (int)m.geti(F_APPTAG, 0);
  if (len) {
    auto it = m.blobs.find(F_PAYLOAD);
    *len = it == m.blobs.end() ? 0 : (int)it->second.size();
  }
  return 1;
}
int ADLB_App_iprobe(int *s_, int *t, int *l) {
  if (!trace_on) return ADLBP_App_iprobe(s_, t, l);
  trace_api_entry();
  double t0 = monotonic();
  int rc = ADLBP_App_iprobe(s_, t, l);
  trace_call("adlb:app_iprobe", t0);
  return rc;
}

int ADLBP_App_recv(void *buf, int maxlen, int *src, int *apptag) {
  if (!g) return ADLB_ERROR;
  for (;;) {
    drain_inbox();
    if (!g->app_inbox.empty()) break;
    poll_inbound(true);
  }
  Msg m = std::move(g->app_inbox.front());
  g->app_inbox.pop_front();
  auto it = m.blobs.find(F_PAYLOAD);
  int n = it == m.blobs.end() ? 0 : (int)it->second.size();
  if (n > maxlen)
    die("App_recv: message of %d bytes exceeds buffer of %d", n, maxlen);
  if (n > 0) memcpy(buf, it->second.data(), (size_t)n);
  if (src) *src = m.src;
  if (apptag) *apptag = (int)m.geti(F_APPTAG, 0);
  return n;
}
int ADLB_App_recv(void *b, int m, int *s_, int *t) {
  if (!trace_on) return ADLBP_App_recv(b, m, s_, t);
  trace_api_entry();
  double t0 = monotonic();
  int rc = ADLBP_App_recv(b, m, s_, t);
  trace_call("adlb:app_recv", t0);
  return rc;
}

// ---- pipelined puts + fused reserve/get (framework extensions) ----------

int ADLBP_Iput(void *work_buf, int work_len, int target_rank, int answer_rank,
               int work_type, int work_prio) {
  if (!g) return ADLB_ERROR;
  if (!valid_type(work_type)) die("Iput of unregistered type %d", work_type);
  if (g->batch_active)
    die("Iput inside Begin_batch_put is not supported (the common-prefix "
        "refcount must be exact)");
  if (target_rank >= 0 && target_rank >= g->num_app_ranks)
    die("Iput target rank %d is not an app rank", target_rank);
  // settle delivered responses, to stay bounded: a look at the sockets is
  // a system call, so every eighth put takes it (responses are some 40
  // bytes; eight of them wait in the kernel meanwhile)
  int64_t id = next_put_id++;
  if (id % 8 == 0) drain_inbox();
  PendingPut &pp = pending_puts[id];
  pp.payload.assign((const char *)work_buf, (size_t)work_len);
  pp.work_type = work_type;
  pp.prio = work_prio;
  pp.target_rank = target_rank;
  pp.answer_rank = answer_rank;
  pp.attempts = 0;
  pp.server = target_rank >= 0 ? home_server(target_rank) : next_server();
  send_iput(id, pp);
  pump_resends();
  return ADLB_SUCCESS;
}
int ADLB_Iput(void *b, int l, int t, int a, int w, int p) {
  if (!trace_on) return ADLBP_Iput(b, l, t, a, w, p);
  trace_api_entry();
  double t0 = monotonic();
  int rc = ADLBP_Iput(b, l, t, a, w, p);
  trace_call("adlb:iput", t0);
  return rc;
}

int ADLBP_Flush_puts(void) {
  if (!g) return ADLB_ERROR;
  for (;;) {
    drain_inbox();
    pump_resends();
    if (pending_puts.empty()) break;
    // every put still pending has a response on its way: sleep in the
    // kernel until a frame arrives (a replay's send may have read some)
    if (g->inbox.empty()) poll_inbound(true);
  }
  int failed = failed_puts;
  bool nmw = failed_nmw;
  failed_puts = 0;
  failed_nmw = false;
  if (nmw) return ADLB_NO_MORE_WORK;
  return failed ? ADLB_PUT_REJECTED : ADLB_SUCCESS;
}
int ADLB_Flush_puts(void) {
  if (!trace_on) return ADLBP_Flush_puts();
  trace_api_entry();
  double t0 = monotonic();
  int rc = ADLBP_Flush_puts();
  trace_call("adlb:flush_puts", t0);
  return rc;
}

int ADLBP_Get_work(int *req_types, int *work_type, int *work_prio,
                   void *work_buf, int max_len, int *work_len,
                   int *answer_rank) {
  // the single-unit call IS a 1-slot batch (scalar out-pointers are
  // 1-element arrays); one copy of the fused/handle fallback logic
  int ng = 0, wl = 0;
  int rc = ADLBP_Get_work_batch(req_types, 1, &ng, work_type, work_prio,
                                work_buf, max_len, &wl, answer_rank);
  if (work_len) *work_len = wl;
  return rc;
}
int ADLBP_Get_work_batch(int *req_types, int max_units, int *num_got,
                         int *work_types, int *work_prios,
                         void *payload_buf, int max_len_per_unit,
                         int *work_lens, int *answer_ranks) {
  if (!g) return ADLB_ERROR;
  if (max_units < 1) die("Get_work_batch: max_units must be >= 1");
  if (num_got) *num_got = 0;
  Msg resp;
  int rc = reserve_impl(req_types, nullptr, nullptr, nullptr, nullptr,
                        nullptr, /*hang=*/1, /*fetch=*/1, &resp, max_units);
  if (rc != ADLB_SUCCESS) return rc;
  char *out = (char *)payload_buf;
  auto blit = resp.blists.find(F_PAYLOADS);
  if (blit != resp.blists.end()) {  // batch-fused: all units consumed
    const std::vector<std::string> &pl = blit->second;
    if ((int)pl.size() > max_units)
      die("Get_work_batch: server sent %zu units for a %d-slot buffer",
          pl.size(), max_units);
    const std::vector<int64_t> &wt = resp.lists[F_WORK_TYPES];
    const std::vector<int64_t> &wp = resp.lists[F_PRIOS];
    const std::vector<int64_t> &ar = resp.lists[F_ANSWER_RANKS];
    for (size_t i = 0; i < pl.size(); i++) {
      int n = (int)pl[i].size();
      if (n > max_len_per_unit)
        die("Get_work_batch: payload of %d bytes exceeds per-unit buffer "
            "of %d", n, max_len_per_unit);
      memcpy(out + (size_t)i * max_len_per_unit, pl[i].data(), (size_t)n);
      if (work_lens) work_lens[i] = n;
      if (work_types && i < wt.size()) work_types[i] = (int)wt[i];
      if (work_prios && i < wp.size()) work_prios[i] = (int)wp[i];
      if (answer_ranks && i < ar.size()) answer_ranks[i] = (int)ar[i];
    }
    trace_last_reserved_wt = wt.empty() ? trace_last_reserved_wt
                                        : (int)wt[0];
    if (num_got) *num_got = (int)pl.size();
    return ADLB_SUCCESS;
  }
  // single-unit shapes (a park wake-up, a remote/prefixed fallback, or a
  // peer that ignores fetch_max)
  if (work_types) work_types[0] = (int)resp.geti(F_WORK_TYPE);
  if (work_prios) work_prios[0] = (int)resp.geti(F_PRIO);
  if (answer_ranks) answer_ranks[0] = (int)resp.geti(F_ANSWER_RANK, -1);
  auto bit = resp.blobs.find(F_PAYLOAD);
  if (bit != resp.blobs.end()) {  // fused single
    // a batch-common unit inlines only its SUFFIX + the prefix handle;
    // assemble prefix + suffix here (one extra fetch per unit — the
    // Python client amortizes it through its prefix cache)
    int common_len = (int)resp.geti(F_COMMON_LEN, 0);
    int n = (int)bit->second.size() + common_len;
    if (n > max_len_per_unit)
      die("Get_work_batch: payload of %d bytes exceeds per-unit buffer of "
          "%d", n, max_len_per_unit);
    char *w = out;
    if (common_len > 0) {
      int prc = fetch_common_prefix((int)resp.geti(F_COMMON_SERVER, -1),
                                    resp.geti(F_COMMON_SEQNO, -1), &w);
      if (prc != ADLB_SUCCESS) return prc;
    }
    memcpy(w, bit->second.data(), bit->second.size());
    if (work_lens) work_lens[0] = n;
    if (num_got) *num_got = 1;
    return ADLB_SUCCESS;
  }
  auto hit = resp.lists.find(F_HANDLE);
  if (hit == resp.lists.end() || hit->second.size() != ADLB_HANDLE_SIZE)
    die("malformed reserve handle");
  int handle[ADLB_HANDLE_SIZE];
  for (int i = 0; i < ADLB_HANDLE_SIZE; i++)
    handle[i] = (int)hit->second[i];
  int wl = (int)resp.geti(F_WORK_LEN);
  if (wl > max_len_per_unit)
    die("Get_work_batch: payload of %d bytes exceeds per-unit buffer of %d",
        wl, max_len_per_unit);
  if (work_lens) work_lens[0] = wl;
  rc = ADLBP_Get_reserved_timed(out, handle, nullptr);
  if (rc == ADLB_SUCCESS && num_got) *num_got = 1;
  return rc;
}
int ADLB_Get_work_batch(int *rt, int max_units, int *ng, int *wt, int *wp,
                        void *b, int mlpu, int *wl, int *ar) {
  if (!trace_on)
    return ADLBP_Get_work_batch(rt, max_units, ng, wt, wp, b, mlpu, wl, ar);
  trace_api_entry();
  double t0 = monotonic();
  int rc = ADLBP_Get_work_batch(rt, max_units, ng, wt, wp, b, mlpu, wl, ar);
  trace_call("adlb:get_work_batch", t0);
  return rc;
}
int ADLB_Get_work(int *rt, int *wt, int *wp, void *b, int ml, int *wl,
                  int *ar) {
  if (!trace_on) return ADLBP_Get_work(rt, wt, wp, b, ml, wl, ar);
  trace_api_entry();
  double t0 = monotonic();
  int rc = ADLBP_Get_work(rt, wt, wp, b, ml, wl, ar);
  trace_call("adlb:get_work", t0);
  if (rc == ADLB_SUCCESS) trace_got_work();
  return rc;
}

// Stamped debug printing (reference src/adlb.c:3395-3417): rank, source
// line and seconds-since-init prefix, gated by both the call-site flag and
// the aprintf_flag given to ADLB_Init.
void adlbp_dbgprintf(int flag, int linenum, const char *fmt, ...) {
  if (!flag || g == nullptr || !g->aprintf_flag) return;
  static double t0 = monotonic();
  fprintf(stderr, "[r=%d] <%d> %.6f: ", g->rank, linenum, monotonic() - t0);
  va_list ap;
  va_start(ap, fmt);
  vfprintf(stderr, fmt, ap);
  va_end(ap);
  fflush(stderr);
}

int ADLB_World_rank(void) { return g ? g->rank : -1; }
int ADLB_World_size(void) { return g ? g->nranks : -1; }
int ADLB_Num_app_ranks(void) { return g ? g->num_app_ranks : -1; }

}  // extern "C"
