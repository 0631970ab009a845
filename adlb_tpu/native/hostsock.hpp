// What two native ranks of one host use between them: a Unix-domain stream
// socket in Linux's abstract namespace, named after the TCP port of the rank
// that listens ("\0adlb_tpu.<port>"), and over it a byte ring in shared
// memory that carries the frames. Shared by libadlb.cpp and serverd.cpp so
// that both ends spell the name, the hello and the ring one way.
//
// Why: every rank of a one-host world, and most peers of a rank in a
// multi-host one, is a process on the same machine, and a loopback TCP
// round trip pays a TCP state machine, an IP layer and an acknowledgement
// in each direction (where the host's network stack runs in user space, as
// under gVisor, that is most of a small frame's round trip). MPI, which the
// reference runs on, carries intra-node messages over shared memory for the
// same reason. Both families are SOCK_STREAM: ordered, reliable, EOF on
// close, so the framing and the reactors above are the same code.
//
// The name: abstract, so there is no file to unlink and it dies with the
// process; scoped to the network namespace exactly as the port is, and
// unique exactly when the port is, so two worlds on one host cannot meet. A
// rank binds it BEFORE its TCP listener becomes reachable, so "TCP
// accepted" implies "Unix was there to try".
//
// Nothing selects it. A sender tries it when the address map says the
// destination is on its own host (the same host string as its own entry,
// or a loopback address) and falls back to TCP when nobody listens there: a
// Python rank (TcpEndpoint: the balancer sidecar, the debug server, Python
// app ranks and servers of a mixed world) has no such listener, and a peer
// in another network namespace cannot be seen. Other hosts get TCP.
//
// What travels where. Connections are one-way (a rank opens one to each rank
// it sends to and reads the ones it accepted), so one single-producer
// single-consumer ring per connection, made by the side that connects (the
// only writer), is the whole topology. The connector makes an anonymous
// segment (memfd_create: no name, so nothing to unlink and nothing left
// behind by a SIGKILL), maps it, and sends a fixed hello of 16 bytes with
// the descriptor attached (SCM_RIGHTS) before anything else; the accepting
// side reads the hello on the connection's first read and maps the segment.
// From then on the FRAMES go through the ring: the same byte stream as on
// a socket, 4-byte length prefix per frame, so parse_frames, the
// `established` policy, the 256 MB cap and T_PEER_EOF are one code path
// above it, and a frame larger than the free space streams through in
// installments. The SOCKET carries two things only: wake-ups (one byte, a
// "bell") and the peer's death (EOF, on which the reader first takes what
// the ring still holds: frames before the death keep their order before
// it). Where the segment cannot be made, the hello says so (ring size 0)
// and the connection carries its bytes on the socket as it did before
// there were rings. A Unix connection that does not begin with a
// well-formed hello is an untrusted stray and is closed. TCP connections
// have no hello and no ring.
//
// A bell is rung only for a sleeper. A reader that is about to sleep in
// poll/epoll marks itself asleep in each of its rings (reader_sleeps),
// issues a full fence, looks at the rings once more, and only then sleeps;
// a writer publishes its tail, issues a full fence, and sends the byte only
// if the mark is set (never blocking: a full socket means a bell is pending
// already). Store, fence, load on both sides: one of the two always sees
// the other, so no timer insures the sleep. Room in a full ring is
// signalled the same way in the other direction (writer_waits; the reader
// sends the byte on the connection it accepted, the socket pair being
// two-way at the kernel), so a daemon's send never blocks and a client
// stuck on a full ring still reads. A put between a client and a server
// that are both awake therefore makes no system call at either end.
//
// The size, one constant (kRingBytes, 64 KiB): the hot rings carry 64-byte
// puts and their answers, a thousand of which fit; one read of a socket
// takes 64 KiB at a time (read_conn's chunk), so a reader's turn on a ring
// is bounded as its turn on a socket is and a flooding peer cannot hold a
// reactor longer than before; and a world of 128 app ranks holds on the
// order of a thousand Unix connections, whose rings are touched only as far
// as bytes flow (a connection that has carried 64 KiB has touched all of
// its ring), so the worst case is 64 KiB x connections, some 70 MB over the
// whole host, where 1 MiB rings (the Python plane's) would be a gigabyte.
// A larger frame pays one bell per 64 KiB, as it paid one wake-up per
// socket buffer. A rank's memory grows by one ring per local peer it talks
// to or hears from.
//
// Also here, because both ends share it: how long a rank that awaits a frame
// looks for it before it sleeps (poll_budget_s). A frame's sender is, as a
// rule, microseconds away (a server answers a put within some 10 us of
// reading it, a synchronous client's next request follows its answer by
// less), and a rank that went to sleep pays the host a wake-up for it:
// tens of microseconds where system calls are answered by a user-space
// kernel. So the client's wait for an answer to its own request
// (libadlb.cpp wait_for) and the daemon's wait after a turn that carried
// traffic (serverd.cpp Endpoint::recv) first take what is there without
// blocking, again and again for this long, and sleep only when nothing
// came. While the frames come through rings a look is a read of each
// ring's tail (memory, one cache line a ring) and the descriptors are asked
// once a budget; while they come over a socket a look is the system call it
// was. The value is transport_shm.py's (_SPIN_S, the Python plane's ring
// poll before it parks on its doorbell), and as there it is 0 on a
// single-core host, where polling only takes the sender's time slice.
// Nothing selects it; what adapts is when a rank enters the phase.

#ifndef ADLB_TPU_HOSTSOCK_HPP
#define ADLB_TPU_HOSTSOCK_HPP

#include <sys/mman.h>
#include <sys/socket.h>
#include <sys/stat.h>
#include <sys/syscall.h>
#include <sys/un.h>
#include <unistd.h>

#include <atomic>
#include <cerrno>
#include <cstddef>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <string>

namespace hostsock {

// Seconds of CLOCK_MONOTONIC a rank polls for an awaited frame before it
// sleeps (above); 0 means it sleeps at once.
inline double poll_budget_s() {
  static const double s = sysconf(_SC_NPROCESSORS_ONLN) > 1 ? 50e-6 : 0.0;
  return s;
}

inline socklen_t unix_name(int port, sockaddr_un* sa) {
  std::memset(sa, 0, sizeof *sa);
  sa->sun_family = AF_UNIX;
  // sun_path[0] stays NUL: the abstract namespace
  int n = std::snprintf(sa->sun_path + 1, sizeof sa->sun_path - 1,
                        "adlb_tpu.%d", port);
  return socklen_t(offsetof(sockaddr_un, sun_path) + 1 + n);
}

// A non-blocking listener on the name of `port`, or -1 with errno set.
// EADDRINUSE means another live process owns the port's name, hence the
// port: fatal to the caller, as a taken TCP port is. Any other failure means
// the platform has no such socket: the rank goes on with TCP alone, and
// peers that try the name find nobody there.
inline int listen_unix(int port, int backlog) {
  int fd = socket(AF_UNIX, SOCK_STREAM | SOCK_NONBLOCK, 0);
  if (fd < 0) return -1;
  sockaddr_un sa;
  socklen_t len = unix_name(port, &sa);
  if (bind(fd, (sockaddr*)&sa, len) != 0 || listen(fd, backlog) != 0) {
    int e = errno;
    close(fd);
    errno = e;
    return -1;
  }
  return fd;
}

// A connection to whoever listens on the name of `port`; -1 when nobody
// does (ECONNREFUSED) or the family is not to be had.
inline int connect_unix(int port) {
  int fd = socket(AF_UNIX, SOCK_STREAM, 0);
  if (fd < 0) return -1;
  sockaddr_un sa;
  socklen_t len = unix_name(port, &sa);
  if (connect(fd, (sockaddr*)&sa, len) != 0) {
    close(fd);
    return -1;
  }
  return fd;
}

// ---- the ring ---------------------------------------------------------------

constexpr uint32_t kRingBytes = 1u << 16;  // the data area (header comment)

// The segment: this header, then kRingBytes of data. Cursors are monotone
// byte counts (a byte's place is cursor % kRingBytes), each on a cache line
// that one side alone writes, published with release stores and read with
// acquire loads. tests/test_native_transport.py plays both ends from Python
// by these offsets: magic 0, data_bytes 8, tail 64, writer_waits 72,
// head 128, reader_sleeps 136, data 256.
struct RingHdr {
  uint64_t magic;       // kRingMagic, set by the connector
  uint32_t data_bytes;  // kRingBytes
  // the writer's line
  alignas(64) std::atomic<uint64_t> tail;   // bytes written, ever
  std::atomic<uint32_t> writer_waits;  // ring a bell when there is room
  // the reader's line
  alignas(64) std::atomic<uint64_t> head;   // bytes taken, ever
  std::atomic<uint32_t> reader_sleeps;  // ring a bell when there are bytes
  alignas(64) char pad[64];
};
static_assert(sizeof(RingHdr) == 256, "the ring's header is four lines");
static_assert(std::atomic<uint64_t>::is_always_lock_free &&
                  std::atomic<uint32_t>::is_always_lock_free,
              "the cursors must be plain memory to both processes");
constexpr uint64_t kRingMagic = 0x31676e6972424c44ull;  // "DLBring1"
constexpr size_t kSegmentBytes = sizeof(RingHdr) + kRingBytes;

// The hello a connector sends first on a Unix connection: 8 bytes of magic,
// the version, and the ring's data size (0: no segment, the bytes follow on
// the socket), with the segment's descriptor attached when there is one.
constexpr size_t kHelloBytes = 16;
constexpr char kHelloMagic[9] = "ADLBring";
constexpr uint32_t kHelloVersion = 1;

// What the rings did for this rank (one rank a process): frames received by
// path, wake-up bytes sent, and publishes that found the reader awake. The
// STATS trailer and the ADLB_TRACE record report them; nothing reads them
// to decide anything.
struct RingStats {
  int64_t frames_ring = 0, frames_sock = 0;
  int64_t bells_rung = 0, bells_elided = 0;
};
inline RingStats& ring_stats() {
  static RingStats s;
  return s;
}

// One byte on the connection's socket, never blocking. A full socket means
// a bell is pending already. False: the peer is gone.
inline bool ring_bell(int fd) {
  char b = 1;
  for (;;) {
    ssize_t r = send(fd, &b, 1, MSG_DONTWAIT | MSG_NOSIGNAL);
    if (r == 1) {
      ++ring_stats().bells_rung;
      return true;
    }
    if (r < 0 && errno == EINTR) continue;
    return r < 0 && (errno == EAGAIN || errno == EWOULDBLOCK);
  }
}

// Take the bells off a socket that carries nothing else (a writer's, where
// the reader rings for room), never blocking. False: the peer is gone.
inline bool drain_bells(int fd) {
  char bells[64];
  ssize_t r = recv(fd, bells, sizeof bells, MSG_DONTWAIT);
  return r > 0 || (r < 0 && (errno == EAGAIN || errno == EWOULDBLOCK ||
                             errno == EINTR));
}

// A descriptor for a new anonymous segment of kSegmentBytes, or -1. A test
// build can make it fail (ADLB_TEST_NO_SEGMENT, a compile-time define of
// tests/test_native_transport.py's own build; nothing at run time does).
inline int make_segment() {
#ifdef ADLB_TEST_NO_SEGMENT
  return -1;
#else
  int fd = int(syscall(SYS_memfd_create, "adlb_tpu.ring", 1u /*MFD_CLOEXEC*/));
  if (fd < 0) return -1;
  if (ftruncate(fd, off_t(kSegmentBytes)) != 0) {
    close(fd);
    return -1;
  }
  return fd;
#endif
}

inline RingHdr* map_segment(int fd) {
  void* p = mmap(nullptr, kSegmentBytes, PROT_READ | PROT_WRITE, MAP_SHARED,
                 fd, 0);
  return p == MAP_FAILED ? nullptr : static_cast<RingHdr*>(p);
}

// The writer's end. Plain data (a copy is the same end; close() unmaps).
class RingTx {
 public:
  bool on() const { return h_ != nullptr; }

  // On a fresh Unix connection: make and map a segment and send the hello
  // with it, or the hello that says there is none. False: the hello could
  // not be sent, the connection is of no use.
  bool open(int fd) {
    close();
    int seg = make_segment();
    if (seg >= 0) {
      h_ = map_segment(seg);
      if (h_ == nullptr) {
        ::close(seg);
        seg = -1;
      }
    }
    if (h_ != nullptr) {
      // a fresh segment reads zero; the reader counts as asleep until it
      // has mapped the ring and says otherwise, so the first frames ring
      h_->magic = kRingMagic;
      h_->data_bytes = kRingBytes;
      h_->reader_sleeps.store(1, std::memory_order_relaxed);
      tail_ = head_ = 0;
      waiting_ = false;
    }
    char hello[kHelloBytes];
    std::memcpy(hello, kHelloMagic, 8);
    uint32_t v = kHelloVersion, n = h_ != nullptr ? kRingBytes : 0;
    std::memcpy(hello + 8, &v, 4);
    std::memcpy(hello + 12, &n, 4);
    iovec iov{hello, sizeof hello};
    msghdr mh{};
    mh.msg_iov = &iov;
    mh.msg_iovlen = 1;
    alignas(cmsghdr) char ctl[CMSG_SPACE(sizeof(int))];
    if (seg >= 0) {
      std::memset(ctl, 0, sizeof ctl);
      mh.msg_control = ctl;
      mh.msg_controllen = sizeof ctl;
      cmsghdr* cm = CMSG_FIRSTHDR(&mh);
      cm->cmsg_level = SOL_SOCKET;
      cm->cmsg_type = SCM_RIGHTS;
      cm->cmsg_len = CMSG_LEN(sizeof(int));
      std::memcpy(CMSG_DATA(cm), &seg, sizeof(int));
    }
    ssize_t r;
    do {
      r = sendmsg(fd, &mh, MSG_NOSIGNAL);
    } while (r < 0 && errno == EINTR);
    if (seg >= 0) ::close(seg);  // the mappings keep the segment
    if (r != ssize_t(sizeof hello)) {
      close();
      return false;
    }
    return true;
  }

  void close() {
    if (h_ != nullptr) munmap(h_, kSegmentBytes);
    h_ = nullptr;
  }

  // Copy as much of p[0..n) as there is room for; the bytes are the
  // reader's only after publish(). Returns the count, 0 when full.
  size_t write(const char* p, size_t n) {
    size_t room = kRingBytes - size_t(tail_ - head_);
    if (room < n) {  // by the cursor last seen: look again
      head_ = h_->head.load(std::memory_order_acquire);
      // a cursor ahead of ours is no rank's of this build: full for ever
      uint64_t used = tail_ - head_;
      room = used > kRingBytes ? 0 : kRingBytes - size_t(used);
    }
    if (n > room) n = room;
    if (n == 0) return 0;
    if (waiting_) {
      h_->writer_waits.store(0, std::memory_order_relaxed);
      waiting_ = false;
    }
    size_t at = size_t(tail_ % kRingBytes);
    size_t first = n < kRingBytes - at ? n : kRingBytes - at;
    char* data = reinterpret_cast<char*>(h_) + sizeof(RingHdr);
    std::memcpy(data + at, p, first);
    std::memcpy(data, p + first, n - first);
    tail_ += n;
    return n;
  }

  // Publish what write() copied and wake the reader if it sleeps (its
  // socket is `fd`). False: the reader is gone.
  bool kick(int fd) {
    h_->tail.store(tail_, std::memory_order_release);
    std::atomic_thread_fence(std::memory_order_seq_cst);
    if (h_->reader_sleeps.load(std::memory_order_relaxed) != 0)
      return ring_bell(fd);
    ++ring_stats().bells_elided;
    return true;
  }

  // The ring is full: ask the reader for a bell when it has made room.
  // True: still full, wait for the byte on the socket. False: room came
  // meanwhile, write on.
  bool wait_room() {
    h_->writer_waits.store(1, std::memory_order_relaxed);
    waiting_ = true;
    std::atomic_thread_fence(std::memory_order_seq_cst);
    head_ = h_->head.load(std::memory_order_acquire);
    return tail_ - head_ >= kRingBytes;
  }

 private:
  RingHdr* h_ = nullptr;
  uint64_t tail_ = 0;  // written, published or not
  uint64_t head_ = 0;  // the reader's cursor when last looked at
  bool waiting_ = false;
};

// The reader's end. Plain data, as RingTx.
class RingRx {
 public:
  bool on() const { return h_ != nullptr; }

  // Map the segment a hello brought; takes the descriptor. False: not a
  // segment of this build's shape.
  bool open(int seg) {
    struct stat st;
    bool ok = fstat(seg, &st) == 0 && size_t(st.st_size) == kSegmentBytes;
    if (ok) h_ = map_segment(seg);
    ::close(seg);
    if (h_ != nullptr &&
        (h_->magic != kRingMagic || h_->data_bytes != kRingBytes))
      close();
    head_ = 0;
    return h_ != nullptr;
  }

  void close() {
    if (h_ != nullptr) munmap(h_, kSegmentBytes);
    h_ = nullptr;
  }

  // Has the writer published bytes not yet taken? One line of memory.
  bool ready() const {
    return h_->tail.load(std::memory_order_acquire) != head_;
  }

  // Append what the ring holds to `buf` and give the room back. Returns the
  // count, or -1 when the cursors make no sense (the peer is no rank of
  // this build). *bell: the writer waits for room and wants a byte.
  ssize_t take(std::string& buf, bool* bell) {
    uint64_t tail = h_->tail.load(std::memory_order_acquire);
    uint64_t n = tail - head_;
    *bell = false;
    if (n == 0) return 0;
    if (n > kRingBytes) return -1;
    size_t at = size_t(head_ % kRingBytes);
    size_t first = n < kRingBytes - at ? size_t(n) : kRingBytes - at;
    const char* data = reinterpret_cast<const char*>(h_) + sizeof(RingHdr);
    buf.append(data + at, first);
    buf.append(data, size_t(n) - first);
    head_ = tail;
    h_->head.store(head_, std::memory_order_release);
    std::atomic_thread_fence(std::memory_order_seq_cst);
    *bell = h_->writer_waits.load(std::memory_order_relaxed) != 0;
    return ssize_t(n);
  }

  // Mark this reader asleep or awake in the writer's sight. Whoever marks
  // itself asleep then issues sleep_fence() and asks ready() once more
  // before it sleeps.
  void sleeps(bool on) {
    h_->reader_sleeps.store(on ? 1 : 0, std::memory_order_relaxed);
  }

 private:
  RingHdr* h_ = nullptr;
  uint64_t head_ = 0;
};

inline void sleep_fence() {
  std::atomic_thread_fence(std::memory_order_seq_cst);
}

// The accepting side's reading of the hello, over as many reads as it takes.
struct HelloRx {
  char buf[kHelloBytes];
  size_t got = 0;
  int seg = -1;  // the descriptor that came with it, until it is mapped
};
enum class Hello { kMore, kBad, kSocket, kRing };

// One non-blocking read toward the hello of an accepted Unix connection.
// kMore: not all there yet. kBad: EOF, an error, or bytes that are no hello
// (judged as far as they have come): close the connection. kSocket: a hello
// without a segment, the frames follow on the socket. kRing: `ring` is
// mapped.
inline Hello recv_hello(int conn, HelloRx& st, RingRx* ring) {
  iovec iov{st.buf + st.got, kHelloBytes - st.got};
  msghdr mh{};
  mh.msg_iov = &iov;
  mh.msg_iovlen = 1;
  alignas(cmsghdr) char ctl[CMSG_SPACE(4 * sizeof(int))];
  mh.msg_control = ctl;
  mh.msg_controllen = sizeof ctl;
  ssize_t r = recvmsg(conn, &mh, MSG_DONTWAIT | MSG_CMSG_CLOEXEC);
  if (r < 0 && (errno == EAGAIN || errno == EWOULDBLOCK || errno == EINTR))
    return Hello::kMore;
  auto bad = [&st] {
    if (st.seg >= 0) ::close(st.seg);
    st.seg = -1;
    return Hello::kBad;
  };
  if (r <= 0) return bad();
  for (cmsghdr* cm = CMSG_FIRSTHDR(&mh); cm != nullptr;
       cm = CMSG_NXTHDR(&mh, cm)) {
    if (cm->cmsg_level != SOL_SOCKET || cm->cmsg_type != SCM_RIGHTS) continue;
    size_t nfd = (cm->cmsg_len - CMSG_LEN(0)) / sizeof(int);
    for (size_t i = 0; i < nfd; ++i) {
      int fd;
      std::memcpy(&fd, CMSG_DATA(cm) + i * sizeof(int), sizeof(int));
      if (st.seg < 0) st.seg = fd;
      else ::close(fd);  // one segment a hello; anything more is dropped
    }
  }
  st.got += size_t(r);
  uint32_t v = 0, n = 0;
  if (st.got >= 12) std::memcpy(&v, st.buf + 8, 4);
  if (std::memcmp(st.buf, kHelloMagic, st.got < 8 ? st.got : 8) != 0 ||
      (st.got >= 12 && v != kHelloVersion))
    return bad();
  if (st.got < kHelloBytes) return Hello::kMore;
  std::memcpy(&n, st.buf + 12, 4);
  if (n == 0) {
    bad();  // a descriptor without a ring is nobody's
    return Hello::kSocket;
  }
  if (n != kRingBytes || st.seg < 0) return bad();
  int seg = st.seg;
  st.seg = -1;
  return ring->open(seg) ? Hello::kRing : Hello::kBad;
}

// Is `host` (an address-map entry) the machine of the rank whose own entry
// is `self`? Judged from the strings alone, no lookup.
inline bool same_host(const std::string& host, const std::string& self) {
  return host == self || host.compare(0, 4, "127.") == 0 ||
         host == "localhost" || host == "::1";
}

}  // namespace hostsock

#endif
